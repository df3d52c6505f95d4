"""What the DeepLearning cell on an all-numeric frame needs of the program,
beside ``bench/harness/system.py`` and ``dl_enum.py`` (whose weight reader
it shares): a training frame of int pixel columns and an enum response, the
class probabilities of a predictions frame, and what the program says of a
job's design.

Like ``system.py`` this module touches the program; nothing else the cell
brings does.
"""

from __future__ import annotations


def install_training_frame(system, key: str, names, cols, y, response: str,
                           response_domain) -> None:
    """Device columns (already row-sharded) -> a resident frame: the pixels
    as int columns (float32 values, as the system stores an int column),
    the response as an enum of its codes."""
    from h2o3_tpu.core.frame import T_CAT, T_INT, Column, code_dtype

    n = int(y.shape[0])
    system._check_rows(n)
    fr = system.h2o.H2OFrame(destination_frame=key)
    for name, c in zip(names, cols):
        fr.add(name, Column.from_device(c, T_INT, n))
    codes = y.astype(code_dtype(len(response_domain)))
    fr.add(response, Column.from_device(codes, T_CAT, n,
                                        domain=list(response_domain)))
    fr.install()


def read_probs(system, frame_key: str, domain):
    """(rows, classes) class probabilities of a predictions frame, its
    columns named by the response's levels."""
    import jax.numpy as jnp

    return jnp.stack([system.read_column(frame_key, level)
                      for level in domain], axis=1)


def design_of(spans: list) -> dict:
    """The attributes the program put on a job's ``design`` span (its
    inputs and the constant columns it dropped); {} without one."""
    for s in spans:
        if s.get("name") == "design":
            return dict(s.get("attrs") or {})
    return {}
