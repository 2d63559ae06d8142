"""Operations and bytes the algorithms need, computed from shapes alone —
the numerators of every roofline and utilization share.

* Evaluate: a query asks for ``n_points`` coordinates of ``dim`` int32
  each, so the least a launch can move is n_points x dim x 4 bytes of
  output, whatever layout the kernel writes.  No FLOPs: the maps are integer
  work on the vector unit, for which the chip publishes no peak.
* Llama decoder (yi-6b): the matrix-multiply parameters of every layer and
  the output head, attention over the positions a token sees, and the bytes
  of all weights plus the key/value rows a decode step reads.
"""
from __future__ import annotations

INT32_BYTES = 4


def evaluate_bytes(n_points: int, dim: int) -> int:
    """Bytes a map launch has to write: the int32 coordinates asked for."""
    return n_points * dim * INT32_BYTES


def _llama(cfg: dict) -> tuple:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hk = cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    ff = cfg["intermediate_size"]
    return d, h, hk, hd, ff, cfg["num_hidden_layers"], cfg["vocab_size"]


def llama_matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matrix product for every token: the
    q/k/v/o and gate/up/down projections of every layer and the output head
    (the embedding is a lookup)."""
    d, h, hk, hd, ff, layers, vocab = _llama(cfg)
    per_layer = d * h * hd + 2 * d * hk * hd + h * hd * d + 3 * d * ff
    return layers * per_layer + d * vocab


def llama_params(cfg: dict) -> int:
    """Every parameter: products, embedding, and the norms' weights."""
    d, *_, layers, vocab = _llama(cfg)
    norms = (2 * layers + 1) * d
    return llama_matmul_params(cfg) + vocab * d + norms


def weight_bytes(cfg: dict) -> int:
    per = 2 if cfg["torch_dtype"] == "bfloat16" else 4
    return llama_params(cfg) * per


def kv_bytes_per_position(cfg: dict) -> int:
    """Key and value bytes of one cached position of one sequence."""
    d, h, hk, hd, ff, layers, vocab = _llama(cfg)
    per = 2 if cfg["torch_dtype"] == "bfloat16" else 4
    return 2 * layers * hk * hd * per


def token_flops(cfg: dict, context: int) -> float:
    """FLOPs of one token that attends to ``context`` positions (itself
    included): two per multiply-add of every product, plus the scores and
    the weighted sum over the context in every head."""
    d, h, hk, hd, ff, layers, vocab = _llama(cfg)
    return 2.0 * llama_matmul_params(cfg) + 4.0 * layers * h * hd * context


def prefill_flops(cfg: dict, rows: int, prompt: int) -> float:
    """A causal prefill: token i of each row sees i + 1 positions."""
    return rows * sum(token_flops(cfg, i + 1) for i in range(prompt))


def decode_flops(cfg: dict, rows: int, position: int) -> float:
    """One decode step whose new token sits at ``position``."""
    return rows * token_flops(cfg, position + 1)


def prefill_bytes(cfg: dict, rows: int, prompt: int) -> int:
    """The least a prefill moves: every weight once except the embedding,
    of which only the prompts' tokens are looked up, and the key/value rows
    it writes for every prompt position."""
    d, *_, vocab = _llama(cfg)
    per = 2 if cfg["torch_dtype"] == "bfloat16" else 4
    looked_up = min(rows * prompt, vocab)
    return weight_bytes(cfg) - (vocab - looked_up) * d * per \
        + rows * prompt * kv_bytes_per_position(cfg)


def decode_bytes(cfg: dict, rows: int, position: int) -> int:
    """The least a decode step reads: every weight once except the
    embedding, of which only each row's token is looked up, and the
    key/value rows of the positions each sequence sees."""
    d, *_, vocab = _llama(cfg)
    per = 2 if cfg["torch_dtype"] == "bfloat16" else 4
    return weight_bytes(cfg) - (vocab - rows) * d * per \
        + rows * (position + 1) * kv_bytes_per_position(cfg)
