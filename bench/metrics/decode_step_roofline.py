"""decode_step_roofline (%; model step, device trace): the least time the
window's engine programs need over the device time they took.

The least time of a decode step is what HBM must read for it — every
weight but the embedding once, the looked-up embedding rows, and the
key/value rows of the positions each sequence sees — at the chip's HBM
bandwidth.  The decode and prefill programs are each one jitted ``lambda``,
so the trace does not name them apart; rather than guess which runs are
which, the share takes every program run in the window (the derive cell
runs nothing else on the device: validation and synthesis are host code)
over the least time of every step and every prefill the window dispatched.
A prefill's least time is the larger of its FLOPs at the bf16 peak and its
bytes at HBM bandwidth; prefills are one call in some hundreds."""
from bench import work


def least_seconds(cfg, peaks, steps, prefills) -> float:
    bw, flops = peaks["hbm_bytes_per_s"], peaks["bf16_flops_per_s"]
    t = sum(work.decode_bytes(cfg, i["rows"], i["position"]) / bw
            for i in steps)
    t += sum(max(work.prefill_flops(cfg, i["rows"], i["prompt"]) / flops,
                 work.prefill_bytes(cfg, i["rows"], i["prompt"]) / bw)
             for i in prefills)
    return t


def read(run):
    if run.trace is None:
        return None
    steps = [i for *_, i in run.spans("engine_step")]
    prefills = [i for *_, i in run.spans("engine_prefill")]
    device_s = sum(s for _, s in run.trace["per_module"].values())
    if not steps or not device_s:
        return None
    need = least_seconds(run.config, run.peaks, steps, prefills)
    return need / device_s * 100.0
