"""Host time per scheduler tick: the mean over the window's
``repro.scheduler.tick`` spans of the tick's duration less its
``device_wait`` spans.  Puts the idle time labelled by the program's
spans, and the tick count, in run.notes."""
import spans


def read(run):
    sp = spans.of(run)
    if sp is None:
        return None
    ms = spans.tick_host_ms(sp)
    if not ms:
        return None
    spans.note_idle(run, sp)
    run.notes["host_ticks"] = len(ms)
    run.notes["host_s"] = sum(ms) / 1e3
    return sum(ms) / len(ms)
