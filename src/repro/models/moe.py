"""Mixture-of-Experts layer with sort-based capacity dispatch (EP-shardable).

Dispatch keeps tensors at (E, C, d) — never (tokens, E, C) — so the layer
compiles at DeepSeek-V2 scale (160 experts, 1M tokens):

  1. router: softmax top-k over expert logits, or DeepSeek-V3's
     ``sigmoid_group`` (noaux_tc) rule,
  2. flatten (token, k) assignments, sort by expert id,
  3. position-within-expert via sorted-segment ranks; drop beyond capacity C,
  4. scatter tokens into (E, C, d), run gated-SwiGLU experts batched over E,
  5. gather back with routing weights; dropped tokens fall through to the
     residual (plus shared experts, which always run densely).

With `experts -> model` sharding the scatter/gather become the all-to-alls
of expert parallelism under SPMD.

A chip's share of an expert-parallel model (``cfg.experts_held`` < E) holds
experts 0..held-1: the router still routes over all E, and the layer
computes only the held experts' part (the rest is other chips' work).
Serving (``moe_serve``) sizes the buffer at the call's token count, so
nothing is dropped, and returns the layer's counters (``COUNTERS``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distribution.sharding import logical_constraint as lc
from repro.models.common import CAP, EMBED, EXPERTS, FFN, dense_init, mlp_init, mlp_specs


#: the serving counters, in the order of the int32 vector ``moe_serve``
#: returns: (token, expert) assignments the router made; those to experts
#: this chip holds; held experts that got at least one token; held experts
#: the layer computed; assignments to held experts dropped for capacity
COUNTERS = ("routed", "to_held", "held_hit", "held_computed", "dropped")


def held_experts(cfg) -> int:
    return getattr(cfg, "experts_held", 0) or cfg.n_experts


def _sigmoid_group(cfg) -> bool:
    return getattr(cfg, "router", "softmax") == "sigmoid_group"


def _expert_weights(key, cfg, in_dim, out_dim, dtype):
    """(in, held, out): one draw per expert from the split of ``key`` over
    all E, so that a share holds exactly the whole model's experts."""
    keys = jax.random.split(key, cfg.n_experts)[:held_experts(cfg)]
    w = jax.vmap(lambda k: dense_init(k, in_dim, out_dim, dtype))(keys)
    return jnp.moveaxis(w, 0, 1)


def moe_init(key, cfg, dtype):
    d, e, f = cfg.d_model, cfg.n_experts, cfg.expert_d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], d, e, jnp.float32),  # router math in fp32
        "gate": _expert_weights(ks[1], cfg, d, f, dtype),
        "up": _expert_weights(ks[2], cfg, d, f, dtype),
        "down": _expert_weights(ks[3], cfg, f, d, dtype),
    }
    if _sigmoid_group(cfg):
        # noaux_tc's selection bias (learned by load statistics, not SGD)
        p["router_bias"] = jnp.zeros((e,), jnp.float32)
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(ks[4], d, cfg.expert_d_ff * cfg.n_shared_experts,
                               dtype)
    return p


def moe_specs(cfg):
    s = {
        "router": (EMBED, EXPERTS),
        "gate": (EMBED, EXPERTS, FFN),
        "up": (EMBED, EXPERTS, FFN),
        "down": (FFN, EXPERTS, EMBED),
    }
    if _sigmoid_group(cfg):
        s["router_bias"] = (None,)
    if cfg.n_shared_experts:
        s["shared"] = mlp_specs()
    return s


def route(p, cfg, logits):
    """fp32 router logits (..., E) -> (weights (..., k), experts (..., k),
    per-expert probabilities (..., E) for the load-balancing loss).

    softmax: top-k of the softmax, optionally renormalised.
    sigmoid_group (DeepSeek-V3's noaux_tc): scores s = sigmoid(logits); the
    choice ranks s + bias: each group scores the sum of its best two, the
    best ``topk_groups`` groups stay, and the top-k of those is chosen; the
    weights are s at the chosen experts (the bias moves the choice only),
    renormalised, times ``routed_scaling``."""
    k = cfg.moe_top_k
    if not _sigmoid_group(cfg):
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_e = jax.lax.top_k(probs, k)
        if cfg.moe_renormalize:
            top_w = top_w / (top_w.sum(axis=-1, keepdims=True) + 1e-9)
        return top_w, top_e, probs
    e, g = logits.shape[-1], cfg.n_groups
    scores = jax.nn.sigmoid(logits)
    choice = scores + p["router_bias"]
    grouped = choice.reshape(*choice.shape[:-1], g, e // g)
    group_score = jax.lax.top_k(grouped, 2)[0].sum(axis=-1)        # (..., g)
    _, kept = jax.lax.top_k(group_score, cfg.topk_groups)
    keep = jax.nn.one_hot(kept, g, dtype=jnp.int32).sum(axis=-2) > 0
    choice = jnp.where(jnp.repeat(keep, e // g, axis=-1), choice, -jnp.inf)
    _, top_e = jax.lax.top_k(choice, k)
    top_w = jnp.take_along_axis(scores, top_e, axis=-1)
    if cfg.moe_renormalize:
        top_w = top_w / (top_w.sum(axis=-1, keepdims=True) + 1e-20)
    top_w = top_w * cfg.routed_scaling
    return top_w, top_e, scores / scores.sum(axis=-1, keepdims=True)


def _require_all_held(cfg, impl: str) -> None:
    if held_experts(cfg) < cfg.n_experts:
        raise ValueError(
            f"moe_impl={impl!r} computes every expert; a share holding "
            f"{held_experts(cfg)} of {cfg.n_experts} runs the global path")


def capacity_for(n_tokens: int, cfg) -> int:
    c = int(n_tokens * cfg.moe_top_k * cfg.capacity_factor / cfg.n_experts)
    return max(-(-c // 8) * 8, 8)  # pad to 8 for TPU sublanes


def moe_apply(p, cfg, x, with_aux: bool = False):
    """x: (B, S, d) -> (B, S, d)  [or (out, aux_loss) with with_aux].

    With cfg.moe_groups > 1 the dispatch is *grouped*: tokens are split into
    G groups aligned with the data axis and scattered group-locally.  Under
    SPMD a global scatter into the (E, C, d) buffer cannot be proven local
    and lowers to giant buffer all-reduces (~53 GB each at DeepSeek scale);
    the grouped form keeps every scatter within one data shard and the only
    EP communication left is the expert-output gather.
    """
    out, aux, _ = _dispatch(p, cfg, x, with_aux, serving=False)
    return (out, aux) if with_aux else out


def moe_serve(p, cfg, x):
    """The serving form: (out, counters) — the int32 vector ``COUNTERS``
    names.  On the single-device path every held expert's buffer holds the
    call's token count, so no assignment is dropped (a prefill of identical
    rows sends every token to the same experts)."""
    out, _, counts = _dispatch(p, cfg, x, False, serving=True)
    return out, counts


def _dispatch(p, cfg, x, with_aux, serving):
    impl = getattr(cfg, "moe_impl", "global")
    if impl == "a2a":
        from repro.distribution.sharding import current_ctx

        ctx = current_ctx()
        if ctx is not None and "model" in ctx.mesh.shape \
                and ctx.mesh.shape["model"] > 1:
            ndev = 1
            for v in ctx.mesh.shape.values():
                ndev *= v
            msh = ctx.mesh.shape["model"]
            # decode steps have fewer tokens than devices — a2a inapplicable
            if (x.shape[0] * x.shape[1]) % ndev == 0 \
                    and cfg.n_experts % msh == 0:
                _require_all_held(cfg, impl)
                return moe_apply_a2a(p, cfg, x, ctx.mesh, with_aux)
    if getattr(cfg, "moe_groups", 1) > 1 or impl == "grouped":
        _require_all_held(cfg, "grouped")
        return _moe_apply_grouped(p, cfg, x, with_aux)
    n = x.shape[0] * x.shape[1]
    return _moe_global(p, cfg, x, n if serving else capacity_for(n, cfg),
                       with_aux)


def _moe_global(p, cfg, x, cap, with_aux):
    """Single-device dispatch over the held experts at capacity ``cap``:
    (out, aux, counters)."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.moe_top_k
    held = held_experts(cfg)
    tokens = x.reshape(b * s, d)
    n = b * s

    # 1. router (fp32)
    logits = jnp.einsum("td,de->te", tokens.astype(jnp.float32), p["router"])
    top_w, top_e, probs = route(p, cfg, logits)             # (n, k)
    aux = jnp.zeros((), jnp.float32)
    if with_aux:  # Switch-style load-balancing loss
        frac = jnp.mean(jax.nn.one_hot(top_e, e, dtype=jnp.float32), axis=(0, 1))
        aux = e * jnp.sum(frac * probs.mean(axis=0))

    # 2. flatten assignments and sort by expert; another chip's experts
    #    share one bucket past the held ones
    flat_e = top_e.reshape(-1)                              # (n*k,)
    if held < e:
        flat_e = jnp.minimum(flat_e, held)
    buckets = held + (held < e)
    flat_t = jnp.repeat(jnp.arange(n), k)                   # token ids
    flat_w = top_w.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]

    # 3. position of each assignment within its expert group
    per_expert = jnp.bincount(se, length=buckets)
    start = jnp.cumsum(per_expert) - per_expert             # group starts
    pos = jnp.arange(n * k) - start[se]                     # rank in group
    keep = pos < cap
    if held < e:
        keep = keep & (se < held)
    slot = jnp.where(keep, se * cap + pos, held * cap)      # drop -> sentinel

    # 4. scatter to (E*C, d), batched expert MLP
    buf = jnp.zeros((held * cap, d), tokens.dtype)
    buf = buf.at[slot].set(tokens[st], mode="drop")
    buf = buf.reshape(held, cap, d)
    buf = lc(buf, "experts", None, None)
    g = jax.nn.silu(jnp.einsum("ecd,def->ecf", buf, p["gate"]))
    u = jnp.einsum("ecd,def->ecf", buf, p["up"])
    out_e = jnp.einsum("ecf,fed->ecd", g * u, p["down"])    # (e, cap, d)
    out_e = lc(out_e, "experts", None, None)

    # 5. gather back with weights; dropped slots contribute zero
    flat_out = out_e.reshape(held * cap, d)
    safe_slot = jnp.minimum(slot, held * cap - 1)
    per_assign = jnp.where(
        keep[:, None], flat_out[safe_slot] * sw[:, None].astype(tokens.dtype),
        0.0,
    )
    out = jnp.zeros((n, d), tokens.dtype).at[st].add(per_assign)

    if cfg.n_shared_experts:
        from repro.models.common import swiglu

        out = out + swiglu(tokens, p["shared"]["gate"], p["shared"]["up"],
                           p["shared"]["down"])
    to_held = jnp.sum(se < held)
    counts = jnp.stack([
        jnp.int32(n * k), to_held.astype(jnp.int32),
        jnp.sum(per_expert[:held] > 0).astype(jnp.int32), jnp.int32(held),
        (to_held - jnp.sum(keep)).astype(jnp.int32)])
    return out.reshape(b, s, d), aux, counts


def _moe_apply_grouped(p, cfg, x, with_aux: bool = False):
    """Group-local dispatch: (G, Tg, d) with G sharded on the data axis.

    All index math (top-k, stable sort, rank-in-expert, capacity drop,
    scatter, gather) is batched over G, so SPMD keeps it shard-local.
    The expert einsum slices E onto the model axis (free — the buffer's E
    dim is replicated per group) and the combine all-gathers expert outputs
    over the model axis — the single intrinsic EP collective.
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.moe_top_k
    g = cfg.moe_groups
    n = b * s
    assert n % g == 0, "token count must split into moe_groups"
    tg = n // g
    cap = capacity_for(tg, cfg)
    toks = x.reshape(g, tg, d)
    toks = lc(toks, "experts_group", None, None)  # G -> data

    logits = jnp.einsum("gtd,de->gte", toks.astype(jnp.float32), p["router"])
    top_w, top_e, probs = route(p, cfg, logits)               # (g, tg, k)
    aux = jnp.zeros((), jnp.float32)
    if with_aux:
        frac = jnp.mean(jax.nn.one_hot(top_e, e, dtype=jnp.float32),
                        axis=(0, 1, 2))
        aux = e * jnp.sum(frac * probs.mean(axis=(0, 1)))

    flat_e = top_e.reshape(g, tg * k)
    flat_t = jnp.broadcast_to(jnp.arange(tg)[:, None], (tg, k)).reshape(-1)
    flat_w = top_w.reshape(g, tg * k)
    order = jnp.argsort(flat_e, axis=1, stable=True)          # (g, tg*k)
    se = jnp.take_along_axis(flat_e, order, axis=1)
    st = flat_t[order]                                        # (g, tg*k)
    sw = jnp.take_along_axis(flat_w, order, axis=1)

    counts = jax.vmap(lambda ee: jnp.bincount(ee, length=e))(se)
    start = jnp.cumsum(counts, axis=1) - counts               # (g, e)
    pos = jnp.arange(tg * k)[None, :] - jnp.take_along_axis(start, se, axis=1)
    keep = pos < cap
    slot = jnp.where(keep, se * cap + pos, e * cap)

    gathered = jnp.take_along_axis(toks, st[..., None], axis=1)  # (g, tg*k, d)
    buf = jnp.zeros((g, e * cap, d), toks.dtype)
    buf = jax.vmap(lambda bb, sl, tk: bb.at[sl].set(tk, mode="drop"))(
        buf, slot, gathered)
    buf = buf.reshape(g, e, cap, d)
    buf = lc(buf, "experts_group", "experts", None, None)     # slice E->model

    gate = jax.nn.silu(jnp.einsum("gecd,def->gecf", buf, p["gate"]))
    up = jnp.einsum("gecd,def->gecf", buf, p["up"])
    out_e = jnp.einsum("gecf,fed->gecd", gate * up, p["down"])
    # the EP combine collective: expert outputs return to their groups
    out_e = lc(out_e, "experts_group", None, None, None)

    flat_out = out_e.reshape(g, e * cap, d)
    safe = jnp.minimum(slot, e * cap - 1)
    picked = jnp.take_along_axis(flat_out, safe[..., None], axis=1)
    per_assign = jnp.where(keep[..., None],
                           picked * sw[..., None].astype(toks.dtype), 0.0)
    out = jnp.zeros((g, tg, d), toks.dtype)
    out = jax.vmap(lambda oo, tt, pa: oo.at[tt].add(pa))(out, st, per_assign)

    if cfg.n_shared_experts:
        from repro.models.common import swiglu

        out = out + swiglu(toks, p["shared"]["gate"], p["shared"]["up"],
                           p["shared"]["down"])
    counters = jnp.stack([
        jnp.int32(n * k), jnp.int32(n * k),
        jnp.sum(counts.sum(axis=0) > 0).astype(jnp.int32), jnp.int32(e),
        (n * k - jnp.sum(keep)).astype(jnp.int32)])
    return out.reshape(b, s, d), aux, counters


# ---------------------------------------------------------------------------
# Explicit all-to-all expert parallelism (shard_map) — §Perf iteration
# ---------------------------------------------------------------------------


def _local_sort_dispatch(ids, n_buckets: int, cap: int):
    """Sort rows by bucket id; returns (slot, keep) with slot = b*cap+pos.

    Pure index math on one shard (no cross-device semantics)."""
    order = jnp.argsort(ids, stable=True)
    sorted_ids = ids[order]
    counts = jnp.bincount(sorted_ids, length=n_buckets)
    start = jnp.cumsum(counts) - counts
    pos = jnp.arange(ids.shape[0]) - start[sorted_ids]
    keep = pos < cap
    slot_sorted = jnp.where(keep, sorted_ids * cap + pos, n_buckets * cap)
    # scatter back to original order
    slot = jnp.zeros_like(slot_sorted).at[order].set(slot_sorted)
    keep_orig = jnp.zeros_like(keep).at[order].set(keep)
    return slot, keep_orig


def moe_apply_a2a(p, cfg, x, mesh, with_aux: bool = False,
                  data_axes=("pod", "data"), model_axis="model"):
    """DeepSeek-style EP: token shards exchange with expert shards via two
    all-to-alls over the model axis (send: token->expert shard; return:
    expert outputs), instead of all-gathering every expert's outputs.

    Collective payload per device ≈ top_k · T_local · d (vs E·C·d for the
    gather-based combine) — the production EP schedule.
    """
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.moe_top_k
    axes_present = tuple(a for a in data_axes if a in mesh.shape)
    dsh = 1
    for a in axes_present:
        dsh *= mesh.shape[a]
    msh = mesh.shape[model_axis]
    n = b * s
    assert n % (dsh * msh) == 0 and e % msh == 0
    t_loc = n // (dsh * msh)
    e_loc = e // msh
    cap_send = max(-(-int(t_loc * k * cfg.capacity_factor) // msh) // 8 * 8, 8)
    cap_loc = max(-(-msh * cap_send // e_loc) // 8 * 8, 8)

    tokens = x.reshape(n, d)

    def device_fn(toks_shard, router_p, gate_w, up_w, down_w):
        router_w = router_p["router"]
        # toks_shard: (n/dsh, d) — replicated over model; take my slice
        j = jax.lax.axis_index(model_axis)
        xt = jax.lax.dynamic_slice_in_dim(toks_shard, j * t_loc, t_loc, 0)
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), router_w)
        top_w, top_e, probs = route(router_p, cfg, logits)
        aux_l = jnp.zeros((), jnp.float32)
        if with_aux:
            frac = jnp.mean(jax.nn.one_hot(top_e, e, dtype=jnp.float32),
                            axis=(0, 1))
            aux_l = e * jnp.sum(frac * probs.mean(0))
            aux_l = jax.lax.pmean(aux_l, model_axis)
            for a in axes_present:
                aux_l = jax.lax.pmean(aux_l, a)

        eid = top_e.reshape(-1)                      # (t_loc*k,)
        tid = jnp.repeat(jnp.arange(t_loc), k)
        wgt = top_w.reshape(-1)
        dest = eid // e_loc
        slot, keep = _local_sort_dispatch(dest, msh, cap_send)
        sendx = jnp.zeros((msh * cap_send, d), xt.dtype)
        sendx = sendx.at[slot].set(xt[tid], mode="drop")
        send_eid = jnp.full((msh * cap_send,), 0, jnp.int32)
        send_eid = send_eid.at[slot].set((eid % e_loc).astype(jnp.int32),
                                         mode="drop")
        send_valid = jnp.zeros((msh * cap_send,), jnp.int32)
        send_valid = send_valid.at[slot].set(1, mode="drop")

        # ---- exchange tokens with expert shards --------------------------
        recvx = jax.lax.all_to_all(sendx.reshape(msh, cap_send, d),
                                   model_axis, 0, 0, tiled=False)
        recv_eid = jax.lax.all_to_all(send_eid.reshape(msh, cap_send),
                                      model_axis, 0, 0, tiled=False)
        recv_valid = jax.lax.all_to_all(send_valid.reshape(msh, cap_send),
                                        model_axis, 0, 0, tiled=False)
        rx = recvx.reshape(msh * cap_send, d)
        rid = recv_eid.reshape(-1)
        rvalid = recv_valid.reshape(-1).astype(bool)
        # invalid rows -> bucket e_loc (dropped by capacity sentinel)
        rid = jnp.where(rvalid, rid, e_loc)
        slot2, keep2 = _local_sort_dispatch(
            jnp.minimum(rid, e_loc), e_loc + 1, cap_loc)
        drop2 = (~keep2) | (rid >= e_loc)
        slot2 = jnp.where(drop2, (e_loc + 1) * cap_loc, slot2)
        buf = jnp.zeros(((e_loc + 1) * cap_loc, d), rx.dtype)
        buf = buf.at[slot2].set(rx, mode="drop")
        buf = buf[: e_loc * cap_loc].reshape(e_loc, cap_loc, d)

        # ---- local expert compute ----------------------------------------
        g_ = jax.nn.silu(jnp.einsum("ecd,def->ecf", buf, gate_w))
        u_ = jnp.einsum("ecd,def->ecf", buf, up_w)
        oute = jnp.einsum("ecf,fed->ecd", g_ * u_, down_w)

        # ---- return path --------------------------------------------------
        flat = oute.reshape(e_loc * cap_loc, d)
        safe2 = jnp.minimum(slot2, e_loc * cap_loc - 1)
        back = jnp.where(drop2[:, None], 0.0, flat[safe2])
        backx = jax.lax.all_to_all(back.reshape(msh, cap_send, d),
                                   model_axis, 0, 0, tiled=False)
        backx = backx.reshape(msh * cap_send, d)
        safe1 = jnp.minimum(slot, msh * cap_send - 1)
        per_assign = jnp.where(keep[:, None],
                               backx[safe1] * wgt[:, None].astype(xt.dtype),
                               0.0)
        out_loc = jnp.zeros((t_loc, d), xt.dtype).at[tid].add(per_assign)
        # reassemble the data shard's tokens across the model axis
        out_shard = jax.lax.all_gather(out_loc, model_axis, axis=0,
                                       tiled=True)
        # counters: drops at either capacity; an expert is hit when any
        # data shard's buffer holds a row for it
        kept2 = rvalid & ~drop2
        dropped = (t_loc * k - jnp.sum(keep)) + jnp.sum(rvalid & drop2)
        hit = jnp.bincount(jnp.where(kept2, rid, e_loc), length=e_loc + 1)
        hit = (hit[:e_loc] > 0).astype(jnp.int32)
        every = (model_axis, *axes_present)
        if axes_present:
            hit = jax.lax.pmax(hit, axes_present)
        counts = jnp.stack([
            jnp.int32(n * k), jnp.int32(n * k),
            jax.lax.psum(jnp.sum(hit), model_axis).astype(jnp.int32),
            jnp.int32(e),
            jax.lax.psum(dropped, every).astype(jnp.int32)])
        return out_shard, aux_l, counts

    tok_spec = P(axes_present if axes_present else None, None)
    w_e_spec = P(None, model_axis, None)
    down_spec = P(None, model_axis, None)
    out_fn = jax.shard_map(
        device_fn, mesh=mesh,
        in_specs=(tok_spec, P(), w_e_spec, w_e_spec, down_spec),
        out_specs=(tok_spec, P(), P()), check_vma=False,
    )
    router_p = {name: p[name] for name in ("router", "router_bias")
                if name in p}
    out, aux, counts = out_fn(tokens, router_p, p["gate"], p["up"],
                              p["down"])
    out = out.reshape(b, s, d)
    if cfg.n_shared_experts:
        from repro.models.common import swiglu

        out = out + swiglu(x, p["shared"]["gate"], p["shared"]["up"],
                           p["shared"]["down"])
    return out, aux, counts
