"""Operations and bytes of a DeepSeek-V3 share (MLA attention, leading
dense layers, routed and shared experts), from the configuration and the
expert layer's counters — the numerators of its roofline and utilization.

* Weights are the configuration's dtype, the router and its bias float32;
  the head is untied.  A MoE layer holds ``n_routed_experts`` of the
  router's ``deployment.router_experts`` experts.
* The MLA cache holds ``kv_lora_rank + qk_rope_head_dim`` values a position
  a layer: 1,152 bytes at bfloat16.
* Routed experts count by what the window's counters saw: their weights
  by the share of held experts that any token hit (``hit_share``), their
  FLOPs by the share of the router's assignments that went to held experts
  (``held_share``).
* Attention counts the expanded form: two multiply-adds a head and a
  position seen, of width nope + rope for the scores and v for the sum.
"""
from __future__ import annotations

COUNTERS = ("moe_routed", "moe_to_held", "moe_held_hit", "moe_held_computed")


def _m(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    ql, kl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return {
        "d": d, "h": h, "dn": dn, "dr": dr, "dv": dv, "layers": layers,
        "dense": dense, "moe": layers - dense,
        "mla": d * ql + ql * h * (dn + dr) + d * kl + kl * h * (dn + dv)
        + d * dr + h * dv * d,
        "norms": layers * (2 * d + ql + kl) + d,
        "ffn": 3 * d * cfg["intermediate_size"],
        "expert": 3 * d * cfg["moe_intermediate_size"],
        "shared": cfg["n_shared_experts"],
        "router": cfg["deployment"]["router_experts"],
        "held": cfg["n_routed_experts"], "k": cfg["num_experts_per_tok"],
        "vocab": -(-cfg["vocab_size"] // 128) * 128,
        "per": 2 if cfg["torch_dtype"] == "bfloat16" else 4,
        "cache": (kl + dr) * (2 if cfg["torch_dtype"] == "bfloat16" else 4),
    }


def expert_shares(counters: dict) -> tuple[float, float] | None:
    """(hit_share, held_share) from the engine counters' change over the
    window; None where the program keeps no such counters or none moved."""
    before = counters.get("before", {}).get("engine", {})
    after = counters.get("after", {}).get("engine", {})
    if any(name not in after for name in COUNTERS):
        return None
    delta = {n: after[n] - before.get(n, 0) for n in COUNTERS}
    if not delta["moe_routed"] or not delta["moe_held_computed"]:
        return None
    return (delta["moe_held_hit"] / delta["moe_held_computed"],
            delta["moe_to_held"] / delta["moe_routed"])


def fixed_bytes(cfg: dict) -> int:
    """Every weight but the embedding and the routed experts: attention,
    norms, dense FFNs, shared experts, routers (float32) and the head."""
    m = _m(cfg)
    bf = m["layers"] * m["mla"] + m["norms"] + m["dense"] * m["ffn"] \
        + m["moe"] * m["shared"] * m["expert"] + m["d"] * m["vocab"]
    return bf * m["per"] + m["moe"] * (m["d"] + 1) * m["router"] * 4


def routed_bytes(cfg: dict, hit_share: float) -> float:
    """The held experts' weights that a pass reads: those hit."""
    m = _m(cfg)
    return m["moe"] * m["held"] * hit_share * m["expert"] * m["per"]


def weight_bytes(cfg: dict) -> int:
    """Every parameter the share holds, the embedding included."""
    m = _m(cfg)
    return fixed_bytes(cfg) + int(routed_bytes(cfg, 1.0)) \
        + m["vocab"] * m["d"] * m["per"]


def decode_bytes(cfg: dict, rows: int, position: int,
                 hit_share: float) -> float:
    """The least a decode step reads: the fixed weights, the held experts
    hit, each row's embedding row, and the MLA cache of the positions each
    sequence sees."""
    m = _m(cfg)
    return fixed_bytes(cfg) + routed_bytes(cfg, hit_share) \
        + rows * m["d"] * m["per"] \
        + rows * (position + 1) * m["layers"] * m["cache"]


def prefill_bytes(cfg: dict, rows: int, prompt: int,
                  hit_share: float) -> float:
    """The least a prefill moves: as a step, with the prompts' embedding
    rows and the cache it writes for every prompt position."""
    m = _m(cfg)
    looked_up = min(rows * prompt, m["vocab"])
    return fixed_bytes(cfg) + routed_bytes(cfg, hit_share) \
        + looked_up * m["d"] * m["per"] \
        + rows * prompt * m["layers"] * m["cache"]


def token_flops(cfg: dict, context: int, held_share: float) -> float:
    """FLOPs of one token that attends to ``context`` positions: two per
    multiply-add of every product it takes part in (routed experts by the
    assignments that went to held ones) and of attention."""
    m = _m(cfg)
    macs = m["layers"] * m["mla"] + m["dense"] * m["ffn"] \
        + m["moe"] * (m["d"] * m["router"] + m["shared"] * m["expert"]
                      + m["k"] * held_share * m["expert"]) \
        + m["d"] * m["vocab"]
    attn = m["layers"] * m["h"] * (m["dn"] + m["dr"] + m["dv"]) * context
    return 2.0 * (macs + attn)


def prefill_flops(cfg: dict, rows: int, prompt: int,
                  held_share: float) -> float:
    """A causal prefill: token i of each row sees i + 1 positions."""
    return rows * sum(token_flops(cfg, i + 1, held_share)
                      for i in range(prompt))


def decode_flops(cfg: dict, rows: int, position: int,
                 held_share: float) -> float:
    """One decode step whose new token sits at ``position``."""
    return rows * token_flops(cfg, position + 1, held_share)
