"""Share of the roofline reached by the decode program, per chip: the
least time the chip needs for a decode step's required work (the
family's ``decode_step_work``: FLOPs, and weight bytes plus the live
tokens' cached bytes), over the step's device time.  The bound that
sets it goes to run.notes."""
import stats
import work


def read(run):
    if run.trace is None:
        return None
    d = run.trace["programs"]["decode"]
    ticks = stats.in_window(run)
    if not d["calls"] or not ticks:
        return None
    running = sum(k[1] for k in ticks) / len(ticks)
    live = sum(k[3] for k in ticks) / len(ticks)
    flops, nbytes = run.family.decode_step_work(run.config, running, live,
                                                run.config["tp"])
    t, bound = work.roofline_seconds(flops, nbytes, run.peaks)
    run.notes["decode_roofline_bound"] = bound
    return 100.0 * t / (d["device_s"] / d["calls"])
