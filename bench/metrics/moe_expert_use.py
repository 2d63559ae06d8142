"""moe_expert_use (%; expert layer, program counter): held experts that any
token reached over held experts the layer computed, summed over the MoE
layers and passes of the cohorts that finished in the window.  The layer
computes every held expert as one dense box, so the rest is waste."""
from bench import work_mla_moe


def read(run):
    shares = work_mla_moe.expert_shares(run.counters)
    return None if shares is None else shares[0] * 100.0
