"""Device time of collectives inside the decode program, over the decode
program's device time."""


def read(run):
    if run.trace is None:
        return None
    d = run.trace["programs"]["decode"]["device_s"]
    if not d or not run.trace["collective_s"]:
        return None
    return 100.0 * run.trace["collective_s"] / d
