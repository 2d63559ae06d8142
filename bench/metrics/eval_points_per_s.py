"""eval_points_per_s (points/s, host clock): coordinates the clients
decoded from evaluate answers, each request weighted by the share of its
time inside the window, over the window's seconds."""


def read(run):
    if not run.answered("evaluate"):
        return None
    return run.prorated("evaluate", "points") / run.seconds
