"""Share of the roofline reached by the decode program, per chip: the
least time the chip needs for a decode step's required work (bench/
work.py: FLOPs, and weight bytes plus the live tokens' KV bytes), over
the step's device time.  The bound that sets it goes to run.notes."""
import stats
import work


def read(run):
    if run.trace is None:
        return None
    d = run.trace["programs"]["decode"]
    ticks = stats.in_window(run)
    if not d["calls"] or not ticks:
        return None
    c, tp = run.config, run.config["tp"]
    running = sum(k[1] for k in ticks) / len(ticks)
    live = sum(k[3] for k in ticks) / len(ticks)
    flops = (running * 2 * (work.body_params(c) + work.head_params(c))
             + live * work.attn_flops(c, 1)) / tp
    nbytes = ((work.body_params(c) + work.head_params(c)) * work.BF16
              + work.kv_bytes_per_token(c) * live) / tp
    t, bound = work.roofline_seconds(flops, nbytes, run.peaks)
    run.notes["decode_roofline_bound"] = bound
    return 100.0 * t / (d["device_s"] / d["calls"])
