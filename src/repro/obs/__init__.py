"""repro.obs — unified swarm telemetry.

One first-class stream for everything the stack reports: structured
spans, typed counters / gauges / histograms, per-flow timeline
batches — recorded by an injectable :class:`Recorder` and consumed by
the JSONL / Perfetto exporters and the ``python -m repro.obs report``
CLI.

Design contract (see docs/OBSERVABILITY.md):

* **Zero overhead when disabled.**  The module-level active recorder
  defaults to a :class:`NullRecorder` whose every hook is a no-op and
  whose ``enabled`` flag is ``False`` — instrumentation sites guard any
  non-trivial argument construction behind ``if rec.enabled:``.
* **Determinism-inert.**  The recorder only *observes*: it draws no
  rng, never feeds back into simulated time, and its measurement clock
  is injectable (``obs.recording(clock=time.perf_counter)``; by default
  a constant zero clock) — so core stays RNG007-clean and determinism
  twins are byte-identical with telemetry on or off.  It is the only
  host-time hook of the sim layer.
* **On the profiler's clock.**  A wall span (``with rec.span(name):``)
  of an enabled recorder is also a ``jax.profiler.TraceAnnotation``,
  so a profiler trace shows the program's phases beside the device's
  operations.
* **One wall clock.**  Simulated instants are recorded round-relative
  and shifted by ``Recorder.time_base`` (set per round by
  :class:`~repro.core.session.SwarmSession` to its ``offsets[-1]``), so
  a multi-round recording lands on the session's single wall clock.
"""
from .recorder import (NullRecorder, Recorder, get, install, recording)
from .export import (read_jsonl, to_jsonl_rows, to_perfetto,
                     validate_rows, write_jsonl, write_perfetto)
from .report import summarize, format_report

__all__ = [
    "NullRecorder", "Recorder", "get", "install", "recording",
    "read_jsonl", "to_jsonl_rows", "to_perfetto", "validate_rows",
    "write_jsonl", "write_perfetto",
    "summarize", "format_report",
]
