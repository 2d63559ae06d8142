"""mfu.eval (%; the whole evaluate path): the least HBM time of every
coordinate delivered in the window (wire hits included), dim x 4 bytes a
point, over the window: the whole path's share of the chip's peak, which
bounds what any kernel-level gain can show end to end."""


def read(run):
    if not run.answered("evaluate"):
        return None
    moved = run.prorated("evaluate", "bytes")
    return moved / run.peaks["hbm_bytes_per_s"] / run.seconds * 100.0
