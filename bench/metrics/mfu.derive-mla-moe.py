"""mfu.derive-mla-moe (%; whole derive path, program spans): FLOPs of every
token the engine processed in the window — prompt tokens in prefill, one a
row in each decode step, counted by ``work_mla_moe`` with routed experts by
the assignments that went to held experts — over the window and the chip's
bf16 peak."""
from bench import work_mla_moe as work


def read(run):
    pre = run.spans("engine_prefill")
    dec = run.spans("engine_step")
    shares = work.expert_shares(run.counters)
    if shares is None or (not pre and not dec):
        return None
    held, cfg = shares[1], run.config
    flops = sum(work.prefill_flops(cfg, i["rows"], i["prompt"], held)
                for *_, i in pre)
    flops += sum(work.decode_flops(cfg, i["rows"], i["position"], held)
                 for *_, i in dec)
    return flops / run.seconds / run.peaks["bf16_flops_per_s"] * 100.0
