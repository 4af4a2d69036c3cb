"""Known-good observability fixture: the same jobs routed through
repro.obs and its injectable clock — no rule may fire."""
from repro import obs


def typed_spray_event(snd, rcv):
    rec = obs.get()
    if rec.enabled:
        rec.event("net.spray", n_snd=len(snd), n_rcv=len(rcv))
    return len(snd)


def typed_counters(rows):
    obs.get().counter("rows_seen", len(rows))


def spanned_timing(fn):
    # Host time flows through the recorder's injectable span clock
    # (obs.recording(clock=...)) — never read inline.
    with obs.get().span("fn"):
        fn()


def referenced_clock_is_fine(clock=None):
    # Referencing (not calling) a clock to inject elsewhere, as
    # obs.recording(clock=time.perf_counter) does, is not a violation.
    import time
    return clock if clock is not None else time.perf_counter
