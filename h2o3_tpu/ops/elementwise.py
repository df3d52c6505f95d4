"""Elementwise distributed column ops.

Reference: each arithmetic/math prim is a full MRTask subclass producing
NewChunks (water/rapids/ast/prims/operators/, math/). TPU-native: a jitted
jnp op on the row-sharded array — GSPMD keeps the sharding, XLA fuses chains
of these into single HBM passes; no explicit map/reduce harness needed.

NA semantics: NaN propagates naturally for numeric ops (H2O NA semantics);
for comparisons, NA rows produce NA (encoded NaN) like H2O."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.core.frame import Column, T_CAT, T_INT, T_NUM


def _as_f32(col: Column):
    """Device f32 view with NaN NAs (enum codes -> float with NaN for -1)."""
    if col.ctype == T_CAT:
        return _cat_to_f32(col.data)
    return col.data


def cat_to_f32_expr(d):
    """Traceable enum-code -> f32 view (NA code -1 -> NaN). The ONE
    definition both the eager jit below and the rapids fusion emitter
    trace through — sharing it is what makes fused statements bitwise
    identical to the eager evaluator by construction."""
    return jnp.where(d >= 0, d.astype(jnp.float32), jnp.nan)


_cat_to_f32 = jax.jit(cat_to_f32_expr)


def bf16_pieces(x):
    """x (f32) as three f32 arrays that a convert to bf16 keeps (the last
    to a rounding) and whose sum is x to its last bit or two:
    reduce_precision, not a convert pair the compiler may take for excess
    precision and drop. Against a one-hot, which bf16 holds exactly, three
    bf16 passes over the pieces give the f32 products (glm._irls_fit's
    Gram, device_tree.leaf_sums)."""
    rp = lambda a: jax.lax.reduce_precision(a, exponent_bits=8,
                                            mantissa_bits=7)
    hi = rp(x)
    mid = rp(x - hi)
    return hi, mid, x - hi - mid


def _trigamma(x):
    """ψ′(x): recurrence ψ′(x)=1/x²+ψ′(x+1) shifted to z=x+8, then the
    asymptotic series 1/z + 1/2z² + 1/6z³ − 1/30z⁵ + 1/42z⁷ — stable in
    f32 (jax.scipy has no polygamma; AstTriGamma parity)."""
    acc = jnp.zeros_like(x)
    z = x
    for _ in range(8):
        acc = acc + 1.0 / (z * z)
        z = z + 1.0
    zi = 1.0 / z
    zi2 = zi * zi
    asym = zi + 0.5 * zi2 + zi * zi2 * (1.0 / 6.0 - zi2 * (1.0 / 30.0
                                                           - zi2 / 42.0))
    return jnp.where(x > 0, acc + asym, jnp.nan)


_BINOPS = {
    "+": jnp.add, "-": jnp.subtract, "*": jnp.multiply, "/": jnp.divide,
    "^": jnp.power, "%": jnp.mod, "intDiv": lambda a, b: jnp.floor_divide(a, b),
}
_CMPOPS = {"==": jnp.equal, "!=": jnp.not_equal, "<": jnp.less,
           "<=": jnp.less_equal, ">": jnp.greater, ">=": jnp.greater_equal}
_UNOPS = {
    "abs": jnp.abs, "exp": jnp.exp, "log": jnp.log, "log2": jnp.log2,
    "log10": jnp.log10, "log1p": jnp.log1p, "expm1": jnp.expm1,
    "sqrt": jnp.sqrt, "floor": jnp.floor, "ceiling": jnp.ceil,
    "round": jnp.round, "trunc": jnp.trunc, "sign": jnp.sign,
    "sin": jnp.sin, "cos": jnp.cos, "tan": jnp.tan,
    "asin": jnp.arcsin, "acos": jnp.arccos, "atan": jnp.arctan,
    "sinh": jnp.sinh, "cosh": jnp.cosh, "tanh": jnp.tanh,
    "asinh": jnp.arcsinh, "acosh": jnp.arccosh, "atanh": jnp.arctanh,
    "cospi": lambda x: jnp.cos(jnp.pi * x),
    "sinpi": lambda x: jnp.sin(jnp.pi * x),
    "tanpi": lambda x: jnp.tan(jnp.pi * x),
    "gamma": lambda x: jnp.exp(jax.scipy.special.gammaln(x)),
    "lgamma": jax.scipy.special.gammaln,
    "digamma": jax.scipy.special.digamma,
    "trigamma": lambda x: _trigamma(x),
    "not": lambda x: jnp.where(jnp.isnan(x), jnp.nan, (x == 0).astype(jnp.float32)),
}


def binop_expr(op: str, a, b):
    """Traceable binary op with H2O NA semantics: arithmetic lets NaN
    propagate; comparisons force NA rows to NA. Shared by the eager
    `binop` jit and the rapids fusion emitter (bitwise parity)."""
    if op in _CMPOPS:
        na = jnp.isnan(a) | jnp.isnan(b)
        return jnp.where(na, jnp.nan,
                         _CMPOPS[op](a, b).astype(jnp.float32))
    return _BINOPS[op](a, b).astype(jnp.float32)


@functools.lru_cache(maxsize=128)
def _jit_binop(op: str, cmp: bool):
    @jax.jit
    def run(a, b):
        return binop_expr(op, a, b)

    return run


def binop(op: str, left, right) -> Column:
    """left/right: Column or scalar. Returns a new numeric/bool Column."""
    cmp = op in _CMPOPS
    lcol = isinstance(left, Column)
    rcol = isinstance(right, Column)
    ref = left if lcol else right
    a = _as_f32(left) if lcol else jnp.float32(left)
    b = _as_f32(right) if rcol else jnp.float32(right)
    out = _jit_binop(op, cmp)(a, b)
    return Column.from_device(out, T_NUM, ref.nrows)


def unop_expr(op: str, a):
    """Traceable unary op (shared eager/fused definition)."""
    return _UNOPS[op](a).astype(jnp.float32)


@functools.lru_cache(maxsize=128)
def _jit_unop(op: str):
    @jax.jit
    def run(a):
        return unop_expr(op, a)

    return run


def unop(op: str, col: Column) -> Column:
    out = _jit_unop(op)(_as_f32(col))
    return Column.from_device(out, T_NUM, col.nrows)


def ifelse_expr(c, a, b):
    """Traceable (ifelse cond yes no): NA cond -> NA (shared eager/fused)."""
    na = jnp.isnan(c)
    return jnp.where(na, jnp.nan, jnp.where(c != 0, a, b))


def logical_expr(op: str, a, b):
    """Traceable `&`/`|` with H2O three-valued-logic NA semantics
    (0 & NA = 0, 1 | NA = 1; else NA poisons). Shared by the eager
    evaluator's logical prims and the fusion emitter."""
    if op == "&":
        return jnp.where((a == 0) | (b == 0), 0.0,
                         jnp.where(jnp.isnan(a) | jnp.isnan(b), jnp.nan,
                                   1.0))
    return jnp.where((a != 0) & ~jnp.isnan(a) | ((b != 0) & ~jnp.isnan(b)),
                     1.0,
                     jnp.where(jnp.isnan(a) | jnp.isnan(b), jnp.nan, 0.0))


def isna_expr(a):
    """Traceable is.na over an f32 view (shared eager/fused). Emitted as
    a select rather than convert(pred): XLA's algebraic simplifier
    rewrites multiply(convert(pred), x) -> select(pred, x, 0), which
    silently drops NaN propagation through 0*NaN when the mask and the
    multiply land in ONE fused program — the select form pins IEEE
    semantics in both evaluation modes."""
    return jnp.where(jnp.isnan(a), jnp.float32(1.0), jnp.float32(0.0))


_ifelse = jax.jit(ifelse_expr)


@functools.lru_cache(maxsize=8)
def _jit_logical(op: str):
    @jax.jit
    def run(a, b):
        return logical_expr(op, a, b)

    return run


def ifelse(cond: Column, yes, no) -> Column:
    a = _as_f32(yes) if isinstance(yes, Column) else jnp.float32(yes)
    b = _as_f32(no) if isinstance(no, Column) else jnp.float32(no)
    return Column.from_device(_ifelse(_as_f32(cond), a, b), T_NUM, cond.nrows)


_isna = jax.jit(isna_expr)


def is_na(col: Column) -> Column:
    if col.ctype == T_CAT:
        return Column.from_device((col.data < 0).astype(jnp.float32), T_NUM, col.nrows)
    if col.data is None:
        vals = np.array([1.0 if v is None else 0.0 for v in col.host_data], np.float32)
        return Column.from_numpy(vals)
    out = _isna(col.data)
    # pad rows are NaN-encoded -> would read as NA=1; zero them out host-side view
    return Column.from_device(out, T_NUM, col.nrows)
