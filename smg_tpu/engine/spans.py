"""The engine's own host spans on the profiler's clock, and the step
account that the same markers keep.

``jax.profiler.TraceAnnotation`` puts a named interval on the calling
thread's line of a running ``jax.profiler`` trace (``/start_profile``), next
to the device's lines, so an idle gap of the device can be put down to the
piece of host work that covered it.  With no trace running an annotation
costs about half a microsecond; the engine takes 20 to 25 steps a second
under load, each of them about ten spans.

A trace is a few seconds once in a while.  ``StepAccount`` is the same
measurement always on: the marker that opens a span of the step thread
(``StepAccount.span``, ``spanned``) also adds the span's
``time.perf_counter()`` seconds to the step's account, so span and counter
cannot drift apart, and the account follows what the host knows of the
chip's queue (``starved``).  ``Scheduler.step`` writes the account to the
step record (``flight_recorder.PHASE_RECORD_KEYS``) and exports its sums once
(``loads()["step_phases"]``, ``smg_engine_step_phase_seconds_total``,
``smg_engine_chip_starved_seconds_total``).

The names are a contract (PERF.md section 3 lists them with the metric each
is for; ``tests/test_engine_tracing.py`` holds them to this tuple).  Three
phases of a step and, inside them, one sub-span for each part whose share a
record was asked for; what is left of a phase is its own: planning (queue,
radix match, pages, eviction) in admit, the host's acceptance in consume,
the decode state's build in launch.  No span per token, per request or per
layer on the host.
"""

from __future__ import annotations

import functools
import time

from jax.profiler import TraceAnnotation

#: ``smg.submit.lock_wait``/``smg.submit`` on the submitting thread; the
#: rest on the step thread, ``smg.step.*`` nested inside ``smg.step`` except
#: ``smg.step.callbacks``, which runs after the step released the engine
#: lock.  ``smg.step.consume.fetch`` is the blocking ``jax.device_get`` of a
#: frame, ``smg.step.admit.pack`` the numpy building of a prefill's operands
#: (scheduler and runner), ``smg.step.admit.dispatch`` the prefill's uploads
#: and jitted call, ``smg.step.launch.dispatch`` those of a decode frame
SPAN_NAMES = (
    "smg.submit.lock_wait", "smg.submit", "smg.step", "smg.step.consume",
    "smg.step.admit", "smg.step.launch", "smg.step.postprocess",
    "smg.step.callbacks", "smg.step.consume.fetch", "smg.step.admit.pack",
    "smg.step.admit.dispatch", "smg.step.launch.dispatch",
)

#: the three phases of a step, and the sub-phases nested in them: the
#: account's word for each span it keeps (``smg.step.a.b`` -> ``a_b``)
TOP_PHASES = ("consume", "admit", "launch")
PHASES = (
    "consume", "consume_fetch", "admit", "admit_pack", "admit_dispatch",
    "launch", "launch_dispatch",
)
#: where starved seconds fall: inside a phase, or in ``other`` (the gap
#: between two steps and what a step does outside the three phases)
STARVED_PHASES = TOP_PHASES + ("other",)

_PHASE_OF = {"smg.step." + p.replace("_", "."): p for p in PHASES}
assert set(_PHASE_OF) <= set(SPAN_NAMES)


class StepAccount:
    """Where the step thread's seconds went, and when the host knew that
    the chip had nothing queued.

    **Phases.**  ``span(name)`` adds the seconds inside it to ``step[phase]``
    (summed where a phase runs twice in a step).  ``begin_step`` zeroes the
    step's numbers, so what a caller outside a step spans (a PD prefill, a
    direct runner call) lands in no record.

    **Starved.**  Every ``*.dispatch`` span is one launch and takes the next
    serial (``launched``) when it returns.  A fetch of launch *n* proves
    every launch up to *n* done: one stream, in order.  The chip is empty
    from the return of a fetch that leaves no later launch outstanding until
    the return of the next dispatch; the interval is cut wherever a phase
    begins or ends and each piece goes to the phase it fell in (``other``
    outside the three).  A discarded frame and a KV-only chunk are never
    fetched and are proved done by the next fetch, so the account errs low;
    it also misses the device-to-host latency of the fetch and the
    enqueue-to-start latency of the dispatch.  It does not see device work
    that goes round the spans (a draft model's proposals): with such work it
    can err high.

    One per scheduler, touched under the engine lock only.  ``clock`` is
    there for tests."""

    def __init__(self, clock=time.perf_counter):
        self.clock = clock
        #: this step's seconds by phase, starved seconds by where they fell,
        #: and the gap before it
        self.step = dict.fromkeys(PHASES, 0.0)
        self.starved = dict.fromkeys(STARVED_PHASES, 0.0)
        self.gap_s = 0.0
        #: sums over every step recorded (``gap`` and ``step`` beside the phases)
        self.totals = dict.fromkeys(PHASES + ("gap", "step"), 0.0)
        self.starved_totals = dict.fromkeys(STARVED_PHASES, 0.0)
        self.steps = 0
        #: serial of the newest launch, and the newest a fetch has proved done
        self.launched = 0
        self._proved = 0
        self._empty_since: float | None = None
        self._phase = "other"
        self._t0 = 0.0
        # where the previous step's record ended, if it left work behind
        self._last_end: float | None = None

    def span(self, name: str, proves: int | None = None) -> "_Span":
        """The span ``name`` (a ``TraceAnnotation``) whose seconds also go to
        this account.  ``proves``: the span is a blocking fetch of the launch
        with that serial."""
        return _Span(self, name, proves)

    def fetched(self, serial: int) -> None:
        """A blocking fetch of launch ``serial`` has returned (one outside
        any ``consume.fetch`` span: a prefill's first tokens)."""
        self._fetched(serial, self.clock())

    def _fetched(self, serial: int, t: float) -> None:
        if serial > self._proved:
            self._proved = serial
        if self._proved >= self.launched and self._empty_since is None:
            self._empty_since = t

    def _dispatched(self, t: float) -> None:
        self._cut(t)
        self._empty_since = None
        self.launched += 1

    def _cut(self, t: float) -> None:
        """Give the empty interval so far to the phase it fell in."""
        if self._empty_since is not None:
            self.starved[self._phase] += t - self._empty_since
            self._empty_since = t

    def begin_step(self) -> float:
        t = self.clock()
        self.step = dict.fromkeys(PHASES, 0.0)
        self.starved = dict.fromkeys(STARVED_PHASES, 0.0)
        if self._last_end is not None:
            self.gap_s = t - self._last_end
            self._cut(t)  # an empty chip starved through the gap too
        else:
            # the engine was idle: no gap, and a chip that is empty starves
            # from here, where there is work for it again
            self.gap_s = 0.0
            self._empty_since = t if self._proved >= self.launched else None
        self._t0 = t
        return t

    def end_step(self, has_work: bool) -> dict:
        """Close the step: its numbers as the step record's keys
        (``flight_recorder.PHASE_RECORD_KEYS`` and ``step_s``)."""
        t = self.clock()
        self._cut(t)
        self._last_end = t if has_work else None
        step, starved = self.step, self.starved
        step_s = t - self._t0
        for k, v in step.items():
            self.totals[k] += v
        self.totals["gap"] += self.gap_s
        self.totals["step"] += step_s
        for k, v in starved.items():
            self.starved_totals[k] += v
        self.steps += 1
        return {
            "step_s": step_s,
            "consume_s": step["consume"], "fetch_wait_s": step["consume_fetch"],
            "admit_s": step["admit"], "admit_pack_s": step["admit_pack"],
            "admit_dispatch_s": step["admit_dispatch"], "launch_s": step["launch"],
            "dispatch_s": step["admit_dispatch"] + step["launch_dispatch"],
            "gap_s": self.gap_s, "starved_s": sum(starved.values()),
            "starved_consume_s": starved["consume"],
            "starved_admit_s": starved["admit"],
            "starved_launch_s": starved["launch"],
        }

    def sums(self) -> dict:
        """``loads()["step_phases"]``."""
        return {"steps": self.steps, "seconds": dict(self.totals),
                "starved_seconds": dict(self.starved_totals)}


class _Span:
    __slots__ = ("acct", "phase", "ann", "proves", "t0", "outer")

    def __init__(self, acct: StepAccount, name: str, proves: int | None):
        self.acct = acct
        self.phase = _PHASE_OF[name]
        self.ann = TraceAnnotation(name)
        self.proves = proves

    def __enter__(self) -> TraceAnnotation:
        self.ann.__enter__()
        a = self.acct
        self.t0 = t = a.clock()
        if self.phase in TOP_PHASES:
            a._cut(t)
            self.outer, a._phase = a._phase, self.phase
        return self.ann

    def __exit__(self, exc_type, exc, tb):
        a, phase = self.acct, self.phase
        t = a.clock()
        a.step[phase] += t - self.t0
        if phase in TOP_PHASES:
            a._cut(t)
            a._phase = self.outer
        elif phase.endswith("_dispatch"):
            a._dispatched(t)
        elif self.proves is not None and exc_type is None:
            a._fetched(self.proves, t)
        return self.ann.__exit__(exc_type, exc, tb)


def spanned(name: str, attrs=None):
    """Run the decorated method inside the span ``name`` of its object's
    ``account``.  ``attrs`` maps the method's result (when not None) to the
    span's attributes; it is called only while a trace runs."""
    assert name in _PHASE_OF, name

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(self, *args, **kwargs):
            with self.account.span(name) as span:
                out = fn(self, *args, **kwargs)
                if attrs is not None and out is not None and span.is_enabled():
                    span.set_metadata(**attrs(out))
                return out

        return wrapper

    return deco
