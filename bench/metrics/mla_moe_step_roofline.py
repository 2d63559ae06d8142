"""mla_moe_step_roofline (%; model step, device trace): the least time the
window's engine programs need (``work_mla_moe``) over the device time of
every program run in the window.

A decode step's least time is its bytes at HBM bandwidth: every weight but
the embedding and the routed experts, the held experts that any token hit,
the looked-up embedding rows and the MLA cache of the positions seen.  A
prefill's is the larger of its FLOPs at the bf16 peak and its bytes.  The
expert shares come from the window's counters; as ``decode_step_roofline``,
every program of the window is counted, since the trace does not name the
prefill and decode programs apart."""
from bench import work_mla_moe as work


def read(run):
    if run.trace is None:
        return None
    shares = work.expert_shares(run.counters)
    steps = [i for *_, i in run.spans("engine_step")]
    prefills = [i for *_, i in run.spans("engine_prefill")]
    device_s = sum(s for _, s in run.trace["per_module"].values())
    if shares is None or not steps or not device_s:
        return None
    hit, held = shares
    cfg, bw = run.config, run.peaks["hbm_bytes_per_s"]
    flops = run.peaks["bf16_flops_per_s"]
    need = sum(work.decode_bytes(cfg, i["rows"], i["position"], hit) / bw
               for i in steps)
    need += sum(max(work.prefill_flops(cfg, i["rows"], i["prompt"], held)
                    / flops,
                    work.prefill_bytes(cfg, i["rows"], i["prompt"], hit) / bw)
                for i in prefills)
    return need / device_s * 100.0
