"""mfu.derive (%; model step): FLOPs of every token the engine processed in
the window — prompt tokens in prefill, one token a row in each decode step,
two per multiply-add of every matrix product plus attention over the
positions each token sees — over the window and the chip's bf16 peak."""
from bench import work


def read(run):
    pre = run.spans("engine_prefill")
    dec = run.spans("engine_step")
    if not pre and not dec:
        return None
    cfg = run.config
    flops = sum(work.prefill_flops(cfg, i["rows"], i["prompt"])
                for *_, i in pre)
    flops += sum(work.decode_flops(cfg, i["rows"], i["position"])
                 for *_, i in dec)
    return flops / run.seconds / run.peaks["bf16_flops_per_s"] * 100.0
