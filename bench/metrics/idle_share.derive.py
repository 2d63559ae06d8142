"""idle_share.derive (%; device, device trace): 1 - the union of the device's
op intervals over the traced window."""


def read(run):
    if run.trace is None or not run.trace["window_s"]:
        return None
    return (1.0 - run.trace["busy_s"] / run.trace["window_s"]) * 100.0
