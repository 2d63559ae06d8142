"""Quickstart: the paper's pipeline end-to-end in ~40 lines.

1. Sample the first 20 points of the 2D triangular domain,
2. "infer" the mapping with an LLM backend (offline replay of gpt-oss:120b),
3. synthesize + validate the generated map_to_coordinates over 10^5 points,
4. deploy the derived map as the Pallas attention grid (mapped vs BB).

Kernels run compiled on a TPU and in Pallas interpret mode elsewhere.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.core.backends import MockLLMBackend, build_prompt
from repro.core.domains import DOMAINS
from repro.core.pipeline import derive_mapping
from repro.kernels.tri_attn.ops import causal_attention, grid_steps
from repro.kernels.tri_attn.ref import causal_attention_ref
from repro.serving.evaluate import auto_interpret

dom = DOMAINS["tri2d"]

print("--- phase 1+2: context sampling & symbolic inference ---")
print(build_prompt(dom, 20).split("<CONTEXT>")[1].split("</CONTEXT>")[0][:300])
res = derive_mapping(dom, MockLLMBackend("OSS:120b"), stage=20,
                     n_validate=100_000)

print("--- phase 3: synthesis + validation ---")
print(res.source)
print(f"ordered={res.report.ordered_pct:.1f}%  "
      f"any-order={res.report.any_order_pct:.1f}%  "
      f"bijective={res.report.bijective}  "
      f"complexity={res.complexity_class}  "
      f"inference energy={res.inference_joules:.0f} J")
assert res.perfect

print("--- phase 4: integration — the map as the attention grid ---")
q, k, v = (jax.random.normal(kk, (1, 4, 512, 64), jnp.float32)
           for kk in jax.random.split(jax.random.PRNGKey(0), 3))
out = causal_attention(q, k, v, 128, 128, "mapped", auto_interpret())
err = float(jnp.max(jnp.abs(out - causal_attention_ref(q, k, v))))
bb, mp = grid_steps(512, 128, "bounding_box"), grid_steps(512, 128, "mapped")
print(f"kernel err vs oracle: {err:.2e}")
print(f"grid steps: bounding-box {bb} -> mapped {mp} "
      f"({1 - mp / bb:.0%} of sequential steps eliminated)")
am = res.amortization()
print(f"deployment: {am.speedup:.0f}x faster, {am.energy_reduction:.0f}x "
      f"less energy, amortized after {am.runs_to_break_even:.1f} runs")
