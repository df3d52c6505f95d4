"""Jobs: async work units with progress/cancel, resident in DKV.

Reference: water/Job.java:23 (progress :184-203), polled by clients via
GET /3/Jobs/{id}. Same lifecycle here: CREATED -> RUNNING -> DONE/FAILED/
CANCELLED, with a progress fraction and message, running on a host thread
(the device work inside is async XLA dispatch anyway).

Crash survivability (hex/Model._checkpoint spirit): a job the cloud
supervisor failed from OUTSIDE (``failed_externally``) is not necessarily
dead — when its trainer persisted durable per-iteration progress
(parallel/ckpt.py job-progress store), the recovery watchdog re-dispatches
it through the RESUMING state: FAILED -> RESUMING -> RUNNING -> DONE, with
``attempt`` counting the dispatches and ``resumed_from_iteration`` naming
where training picked back up (both on GET /3/Jobs). Jobs also survive
control-plane checkpoints: pickling drops the live thread and lock, so a
standby coordinator restores the job METADATA and the watchdog rebuilds
the rest from the progress file.
"""

from __future__ import annotations

import threading
import time
import traceback
from typing import Any, Callable, Optional

from h2o3_tpu.core.dkv import DKV, Key, Keyed
from h2o3_tpu.obs import tracing


class JobCancelled(Exception):
    pass


class Job(Keyed):
    CREATED, RUNNING, DONE, FAILED, CANCELLED = "CREATED", "RUNNING", "DONE", "FAILED", "CANCELLED"
    # externally-failed job being re-dispatched from durable progress
    RESUMING = "RESUMING"

    def __init__(self, description: str = "", dest: Optional[str] = None):
        super().__init__(Key.make("Job"))
        self.description = description
        self.dest = dest  # key of the result object
        self.status = Job.CREATED
        self.progress = 0.0
        self.progress_msg = ""
        self.exception: Optional[str] = None
        # True when the cloud supervisor failed this job from outside
        # (dead follower / cloud FAILED) rather than the worker crashing:
        # such a job stays FAILED across a later cloud recovery UNLESS it
        # persisted durable training progress — then the watchdog resumes
        # it (restart() below); everything else is resubmitted by clients
        self.failed_externally = False
        # dispatch count (1 = original submit) and, on a resume, the
        # iteration training continued from — both on GET /3/Jobs
        self.attempt = 1
        self.resumed_from_iteration: Optional[int] = None
        # re-dispatch recipe (algo, wire params, frame keys, response,
        # destination) attached by the REST train handler when durable
        # progress is enabled; JSON-only so it survives pickling
        self.resume_spec: Optional[dict] = None
        self.start_time = 0.0
        self.end_time = 0.0
        self._cancel_requested = False
        self._thread: Optional[threading.Thread] = None
        # serializes terminal-status writes: the worker thread's DONE and
        # the cloud supervisor's external FAILED must not interleave
        self._status_lock = threading.Lock()
        self.result: Any = None
        self.install()

    # -- control-plane checkpoint survival --------------------------------
    # a Job rides the DKV, so it is pickled into oplog checkpoints; the
    # live thread and lock are process-local and must not sink the whole
    # per-key snapshot (they used to — jobs landed in the 'skipped' list
    # and a standby coordinator lost every job's metadata)
    def __getstate__(self):
        d = dict(self.__dict__)
        d["_thread"] = None
        d.pop("_status_lock", None)
        d["result"] = None          # results live under their own DKV key
        return d

    def __setstate__(self, d):
        self.__dict__.update(d)
        self._thread = None
        self._status_lock = threading.Lock()
        if self.status in (Job.CREATED, Job.RUNNING, Job.RESUMING):
            # an unpickled job has NO worker thread by construction: it was
            # in flight when the snapshot was taken and that work died with
            # its process. Mark it externally failed so it either resumes
            # (durable progress) or reports honestly — a restored RUNNING
            # job with no thread would otherwise stay RUNNING forever.
            self.status = Job.FAILED
            self.failed_externally = True
            self.end_time = self.end_time or time.time()
            self.exception = self.exception or (
                "job was in flight when its process died; restored from a "
                "control-plane checkpoint (the recovery watchdog resumes "
                "it if durable training progress exists)")

    # -- driver side ------------------------------------------------------
    def start(self, fn: Callable[["Job"], Any], background: bool = True) -> "Job":
        """Run fn(job) (the Driver.computeImpl analog, hex/ModelBuilder.java:224)."""
        # dispatch generation: restart() bumps `attempt`, so a STALE worker
        # thread from a pre-restart dispatch (e.g. one that was wedged in a
        # dead collective when the supervisor failed the job) can never
        # write this job's verdict or result once a resume is in flight
        gen = self.attempt
        # the caller's trace (a REST POST's ``ingress``) follows the work
        # onto the worker thread: span ``job`` and whatever `fn` opens
        # beneath it land in that trace, after the POST has returned
        ctx = tracing.context()

        def run():
            with tracing.activate(ctx), \
                    tracing.span("job", job=str(self.key),
                                 description=self.description) as sp:
                work()
                sp.set(status=self.status)

        def work():
            with self._status_lock:
                if self.status == Job.FAILED or self.attempt != gen:
                    # the supervisor failed this job while still CREATED
                    # (cloud died between submit and thread start): honor
                    # the verdict, never run work against a dead cloud
                    return
                self.status = Job.RUNNING
            self.start_time = time.time()
            try:
                result = fn(self)
                with self._status_lock:
                    if self.status == Job.FAILED or self.attempt != gen:
                        # the supervisor declared this job dead (cloud
                        # FAILED) while in flight: keep that verdict and
                        # do NOT install the result — it was built
                        # against a diverged cloud
                        return
                    self.result = result
                    if self.dest and result is not None:
                        DKV.put(self.dest, result)
                    self.status = Job.DONE
                    self.progress = 1.0
                    # a completed resume supersedes the old verdict
                    self.failed_externally = False
            except JobCancelled:
                with self._status_lock:
                    if self.status != Job.FAILED and self.attempt == gen:
                        self.status = Job.CANCELLED
            except Exception:
                with self._status_lock:
                    if self.status != Job.FAILED and self.attempt == gen:
                        # a supervisor verdict (remote traceback) already
                        # landed: keep it — the worker's own exception is
                        # a downstream symptom of the same cloud failure
                        self.exception = traceback.format_exc()
                        self.status = Job.FAILED
            finally:
                if self.attempt == gen:
                    self.end_time = time.time()

        if background:
            self._thread = threading.Thread(target=run, daemon=True)
            self._thread.start()
        else:
            run()
        return self

    def update(self, progress: float, msg: str = "") -> None:
        """Progress tick; raises if a cancel was requested (cooperative)."""
        if self._cancel_requested:
            raise JobCancelled()
        self.progress = float(progress)
        if msg:
            self.progress_msg = msg

    def fail(self, exception_text: str) -> None:
        """Mark FAILED from OUTSIDE the worker thread (cloud supervisor,
        degraded mode): the worker may be wedged inside a dead collective
        and never unwind to record its own failure. No-op once terminal;
        the status lock keeps a worker unwinding at the same instant from
        overwriting the verdict with DONE."""
        with self._status_lock:
            if not self.is_running:
                return
            self.exception = exception_text
            self.failed_externally = True
            self.status = Job.FAILED
            self.end_time = time.time()

    # -- locked terminal transitions for SYNCHRONOUS drivers --------------
    # ModelBuilder.train() runs without Job.start's wrapper; these keep its
    # status writes under the same lock so its DONE can never land on top
    # of a supervisor's external FAILED (the fail()/completion race)
    def begin(self) -> bool:
        """CREATED/RESUMING -> RUNNING; False when the supervisor already
        failed the job (the caller must not run work against a dead cloud)."""
        with self._status_lock:
            if self.status == Job.FAILED:
                return False
            self.status = Job.RUNNING
            self.start_time = time.time()
            return True

    def complete(self) -> bool:
        """RUNNING -> DONE under the status lock; False (verdict kept) when
        an external FAILED already landed."""
        with self._status_lock:
            if self.status == Job.FAILED:
                return False
            self.status = Job.DONE
            self.progress = 1.0
            self.failed_externally = False
            self.end_time = time.time()
            return True

    def fail_local(self, exception_text: str) -> None:
        """Worker-side failure under the status lock; an earlier external
        verdict (with the remote traceback) is kept."""
        with self._status_lock:
            if self.status != Job.FAILED:
                self.exception = exception_text
                self.status = Job.FAILED
            self.end_time = time.time()

    def restart(self, resumed_from_iteration: Optional[int] = None) -> bool:
        """FAILED(externally) -> RESUMING for a re-dispatch from durable
        progress. Atomic under the status lock so two recovery passes can
        never double-dispatch one job; False when the job is not an
        externally-failed candidate."""
        with self._status_lock:
            if self.status != Job.FAILED or not self.failed_externally:
                return False
            self.status = Job.RESUMING
            self.attempt += 1
            self.failed_externally = False
            self.exception = None
            self.end_time = 0.0
            if resumed_from_iteration is not None:
                self.resumed_from_iteration = int(resumed_from_iteration)
            return True

    # -- client side ------------------------------------------------------
    def cancel(self) -> None:
        self._cancel_requested = True

    def join(self, timeout: Optional[float] = None) -> "Job":
        if self._thread is not None:
            self._thread.join(timeout)
        if self.status == Job.FAILED:
            raise RuntimeError(f"Job {self.key} failed:\n{self.exception}")
        return self

    @property
    def is_running(self) -> bool:
        return self.status in (Job.CREATED, Job.RUNNING, Job.RESUMING)

    def to_dict(self) -> dict:
        return {
            "key": str(self.key),
            "description": self.description,
            "status": self.status,
            "progress": self.progress,
            "progress_msg": self.progress_msg,
            "dest": self.dest,
            "exception": self.exception,
            "failed_externally": self.failed_externally,
            "attempt": self.attempt,
            "resumed_from_iteration": self.resumed_from_iteration,
            "start_time": self.start_time,
            "end_time": self.end_time,
        }
