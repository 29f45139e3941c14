"""Hung-engine supervisor: escalate the dispatch watchdog from gauge to act.

PR 4 gave the BatchEngine a watchdog *reading* — `batch_dispatch_age_seconds`,
seconds since the scheduler last completed a device dispatch while work is in
flight — but nothing consumed it: a wedged engine (a dispatch hung in the
backend, where even a trivial op never completes) sat at 100% unavailability
while /healthz kept answering
"ok" and every queued client waited forever.

The EngineSupervisor closes that loop (docs/ROBUSTNESS.md "Hung-engine
supervision"). A daemon thread polls `engine.dispatch_age()`; when the age
crosses `threshold` seconds it escalates:

1. flip this supervisor (and therefore the replica's /healthz, which
   api_server wires to `healthy`) UNHEALTHY — a fleet router ejects the
   replica within one membership poll and resumes its journaled in-flight
   requests on surviving replicas (docs/FLEET.md "Resume protocol");
2. call `engine.recover_wedged()`: fail every in-flight/queued request with
   the RETRIABLE EngineWedged, abandon the stuck scheduler thread (engine
   epoch bump), and re-initialize the backend (drop compiled programs,
   fresh KV caches);
3. on successful re-init, flip healthy again — the replica rejoins rotation
   on the router's next clean poll. `max_recoveries` consecutive escalations
   without an intervening healthy period marks the engine FAILED: /healthz
   stays unhealthy so the operator (or the orchestrator's restart policy)
   takes over instead of the supervisor thrashing a dead backend.

The supervisor never *prevents* a wedge — it bounds the blast to
`threshold + poll` seconds of stall followed by retriable failures, instead
of an unbounded silent outage.
"""

from __future__ import annotations

import threading
import time

from ..obs import metrics

__all__ = ["EngineSupervisor"]

_STATE = metrics.gauge(
    "engine_supervisor_state",
    "Hung-engine supervisor state: 0 ok, 1 recovering, 2 failed "
    "(docs/ROBUSTNESS.md)")

_STATES = {"ok": 0, "recovering": 1, "failed": 2}


class EngineSupervisor:
    """Watch one BatchEngine-shaped object (`dispatch_age()`,
    `recover_wedged()`, `scheduler_alive()`) and act on a hang.

    `threshold` — dispatch age (seconds) past which the engine counts as
    wedged; size it well above the slowest legitimate dispatch (a prefill
    chunk on cold compile can take tens of seconds on first use).
    `poll` — watchdog sampling period; detection latency is threshold+poll.
    `max_recoveries` — consecutive recoveries (no healthy dispatch observed
    between them) before the supervisor gives up and stays unhealthy.
    `reinit` — forward to recover_wedged (tests disable to isolate the
    abandon/fail half).
    """

    def __init__(self, engine, threshold: float = 60.0, poll: float = 1.0,
                 max_recoveries: int = 3, reinit: bool = True):
        assert threshold > 0, "use threshold>0 (0 disables the supervisor)"
        self.engine = engine
        self.threshold = float(threshold)
        self.poll = float(poll)
        self.max_recoveries = max_recoveries
        self.reinit = reinit
        self.state = "ok"  # ok | recovering | failed
        self.recoveries = 0  # lifetime escalations
        self._consecutive = 0  # escalations without dispatch progress between
        self._progress_mark = self._progress()
        self.last_recovery_t: float | None = None
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        _STATE.set(0)

    # ------------------------------------------------------------------

    @property
    def healthy(self) -> bool:
        """False while a recovery is in progress or the engine is failed —
        the reading api_server's /healthz folds in so the router ejects the
        replica for exactly the unhealthy window."""
        return self.state == "ok"

    def stats(self) -> dict:
        return {"state": self.state, "threshold_s": self.threshold,
                "recoveries": self.recoveries,
                "dispatch_age_s": round(self.engine.dispatch_age(), 3)}

    # ------------------------------------------------------------------

    def start(self) -> "EngineSupervisor":
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(target=self._run, daemon=True,
                                            name="engine-supervisor")
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=self.poll + 1.0)

    def _run(self) -> None:
        while not self._stop.wait(self.poll):
            try:
                self.check_once()
            except Exception as e:  # the supervisor itself must not die
                print(f"⚠️  supervisor check failed: {e!r}")

    def _progress(self) -> tuple:
        """Dispatch-progress reading: counters that only a COMPLETED device
        dispatch advances. The consecutive-escalation guard keys on these —
        an idle age of 0 right after a recovery (slots just cleared) is NOT
        evidence the engine works, so it must not reset the counter or a
        permanently broken backend would thrash ok→wedged forever instead
        of reaching the terminal 'failed' state."""
        eng = self.engine
        return (getattr(eng, "decode_steps", 0),
                getattr(eng, "prefilled_tokens", 0))

    def check_once(self) -> None:
        """One watchdog sample + escalation decision (called from the loop;
        tests call it directly for deterministic timing)."""
        if self.state == "failed":
            return
        age = self.engine.dispatch_age()
        if age <= self.threshold:
            if self._consecutive and self._progress() != self._progress_mark:
                # real dispatches completed since the last escalation:
                # isolated wedges spread over a long uptime never
                # accumulate into a spurious "failed"
                self._consecutive = 0
            return
        self._escalate(age)

    def _escalate(self, age: float) -> None:
        self._set_state("recovering")
        self.recoveries += 1
        self._consecutive += 1
        self._progress_mark = self._progress()
        self.last_recovery_t = time.monotonic()
        print(f"🔴 supervisor: engine made no dispatch progress for "
              f"{age:.1f}s (threshold {self.threshold:.1f}s) — failing "
              f"in-flight requests (retriable) and re-initializing "
              f"(recovery {self._consecutive}/{self.max_recoveries})")
        ok = False
        try:
            ok = self.engine.recover_wedged(reinit=self.reinit)
        except Exception as e:
            print(f"🔴 supervisor: recover_wedged raised: {e!r}")
        if not ok or self._consecutive >= self.max_recoveries:
            self._set_state("failed")
            print("🔴 supervisor: engine marked FAILED "
                  f"(reinit_ok={ok}, consecutive={self._consecutive}) — "
                  "/healthz stays unhealthy; restart the replica")
        else:
            self._set_state("ok")

    def _set_state(self, state: str) -> None:
        self.state = state
        _STATE.set(_STATES[state])
