"""Mixture-of-Experts layer (DeepSeek-style: shared + routed, top-k).

Dispatch is capacity-based (GShard/Switch lineage) and implemented with a
sort → padded per-expert blocks → batched matmul pipeline, which shards
cleanly over an expert axis and keeps HLO FLOPs ≈ active FLOPs
(overprovisioned by ``capacity_factor``). Tokens overflowing an expert's
capacity are dropped (standard); the router carries a load-balance loss.

An alternative ``dispatch="dense"`` path (one-hot einsum over all experts)
exists for tiny smoke configs and as the naive baseline in the §Perf
hillclimb; it is O(E) compute and must not be used at scale.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import _act, dense_init, mlp_apply, mlp_init
from repro.parallel.sharding import constrain


def constrain_expert_batched(x):
    """(B, E, C, D) dispatch blocks — mirror the *weight* expert sharding
    (§Perf iteration B1): when E divides the full (fsdp×model) product the
    weights are 256-way expert-parallel, so the blocks must be too (B
    replicated → GSPMD emits the canonical MoE all-to-all); otherwise E
    rides the model axis and B keeps fsdp."""
    from repro.parallel.sharding import activation_mesh, fsdp_axes

    mesh = activation_mesh()
    if mesh is None:
        return x
    fs = fsdp_axes(mesh)
    full = 1
    for a in tuple(fs) + ("model",):
        full *= mesh.shape[a]
    e = x.shape[1]
    if e % full == 0 and e >= full:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec = P(None, tuple(fs) + ("model",), None, None)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    return constrain(x, ("fsdp", "model", None, None))


def moe_init(key, d_model, num_experts, d_ff_expert, num_shared, d_ff_shared, dtype) -> Dict:
    kr, k1, k2, k3, ks = jax.random.split(key, 5)
    s_in = d_model**-0.5
    s_out = d_ff_expert**-0.5
    p = {
        "router": dense_init(kr, (d_model, num_experts), dtype=jnp.float32),
        "w1": (jax.random.normal(k1, (num_experts, d_model, d_ff_expert)) * s_in).astype(dtype),
        "w2": (jax.random.normal(k2, (num_experts, d_ff_expert, d_model)) * s_out).astype(dtype),
        "w3": (jax.random.normal(k3, (num_experts, d_model, d_ff_expert)) * s_in).astype(dtype),
    }
    if num_shared > 0:
        p["shared"] = mlp_init(ks, d_model, d_ff_shared, gated=True, dtype=dtype)
    return p


def router_probs(p, x):
    logits = (x.astype(jnp.float32) @ p["router"]).astype(jnp.float32)
    return jax.nn.softmax(logits, axis=-1)


def load_balance_loss(probs, top_idx, num_experts):
    """Switch-style aux loss: E · Σ_e f_e · P_e."""
    t = probs.shape[0]
    onehot = jax.nn.one_hot(top_idx, num_experts, dtype=jnp.float32)  # (t, k, E)
    f = onehot.sum(axis=(0, 1)) / jnp.maximum(top_idx.size, 1)
    pbar = probs.mean(axis=0)
    return num_experts * jnp.sum(f * pbar)


def _capacity(tokens: int, top_k: int, num_experts: int, factor: float) -> int:
    c = int(math.ceil(tokens * top_k / num_experts * factor))
    return max(c, 4)


def _local_dispatch(xt, top_i, top_w, e: int, cap: int):
    """Capacity scatter of one device's tokens into (E·cap+1, D) slots.

    Returns (buf, dest, tok, w_sorted, keep) — shared by the GSPMD row-wise
    path (vmapped over rows) and the shard_map a2a path (per device).
    """
    t, k = top_i.shape
    d = xt.shape[-1]
    sk = t * k
    flat_e = top_i.reshape(sk)
    flat_w = top_w.reshape(sk)
    order = jnp.argsort(flat_e)
    sorted_e = flat_e[order]
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    pos = jnp.arange(sk) - first
    keep = pos < cap
    dest = jnp.where(keep, sorted_e * cap + pos, e * cap)
    tok = order // k
    buf = jnp.zeros((e * cap + 1, d), xt.dtype).at[dest].set(xt[tok])
    w_sorted = (flat_w[order] * keep)
    return buf, dest, tok, w_sorted


def moe_apply_a2a(p: Dict, x, *, top_k: int, activation: str,
                  capacity_factor: float):
    """Expert-parallel MoE with an explicit all-to-all (shard_map).

    §Perf iteration B2: GSPMD cannot infer token-exchange from a scatter
    formulation — it either reshards the expert weights every layer
    (baseline) or replicates the token batch (B1, refuted). This is the
    production pattern: tokens stay sharded (batch over fsdp, sequence
    over model), each device scatters its own tokens into per-expert-home
    capacity slots, ONE all-to-all ships them to the expert homes, dense
    local matmuls run, one all-to-all ships results back.

    Returns None when the layout prerequisites don't hold (caller falls
    back to the GSPMD row-wise path) — e.g. decode steps with seq 1.
    """
    from jax.sharding import PartitionSpec as P

    from repro.parallel.sharding import (
        activation_mesh, expert_axis_candidates, fsdp_axes)

    mesh = activation_mesh()
    if mesh is None or x.ndim != 3:
        return None
    bsz, s, d = x.shape
    e = p["w1"].shape[0]
    fs = fsdp_axes(mesh)
    fsdp_sz = 1
    for a in fs:
        fsdp_sz *= mesh.shape[a]
    model_sz = mesh.shape["model"]
    ex_axes = None
    for cand in expert_axis_candidates(mesh):
        sz = 1
        for a in cand:
            sz *= mesh.shape[a]
        if sz > 1 and e % sz == 0:
            ex_axes = cand
            g = sz
            break
    if ex_axes is None or bsz % fsdp_sz or s % model_sz:
        return None
    eph = e // g
    t_local = (bsz // fsdp_sz) * (s // model_sz)
    cap = _capacity(t_local, top_k, e, capacity_factor)
    act = _act(activation)
    fsdp_entry = fs if len(fs) > 1 else fs[0]
    ex_entry = ex_axes if len(ex_axes) > 1 else ex_axes[0]
    all_axes = tuple(mesh.axis_names)

    def local_fn(xl, router, w1, w2, w3):
        xt = xl.reshape(t_local, d)
        probs = jax.nn.softmax((xt.astype(jnp.float32) @ router), axis=-1)
        top_w, top_i = jax.lax.top_k(probs, top_k)
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
        aux = load_balance_loss(probs, top_i, e)
        aux = jax.lax.pmean(aux, all_axes)

        buf, dest, tok, w_sorted = _local_dispatch(xt, top_i, top_w, e, cap)
        send = buf[: e * cap].reshape(g, eph * cap, d)
        recv = jax.lax.all_to_all(send, ex_axes, split_axis=0, concat_axis=0,
                                  tiled=True)  # (g_src, eph·cap, d)
        blocks = recv.reshape(g, eph, cap, d).transpose(1, 0, 2, 3)
        blocks = blocks.reshape(eph, g * cap, d)
        h = jnp.einsum("egd,edf->egf", blocks, w1)
        h = act(h) * jnp.einsum("egd,edf->egf", blocks, w3)
        y = jnp.einsum("egf,efd->egd", h, w2)
        y = y.reshape(eph, g, cap, d).transpose(1, 0, 2, 3).reshape(g, eph * cap, d)
        back = jax.lax.all_to_all(y, ex_axes, split_axis=0, concat_axis=0,
                                  tiled=True).reshape(e * cap, d)
        back = jnp.concatenate([back, jnp.zeros((1, d), back.dtype)], axis=0)
        contrib = back[dest] * w_sorted[:, None].astype(back.dtype)
        out = jnp.zeros((t_local, d), xl.dtype).at[tok].add(contrib)
        return out.reshape(xl.shape), aux

    out, aux = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(fsdp_entry, "model", None), P(None, None),
                  P(ex_entry, None, None), P(ex_entry, None, None),
                  P(ex_entry, None, None)),
        out_specs=(P(fsdp_entry, "model", None), P()),
    )(x, p["router"], p["w1"], p["w2"], p["w3"])
    return out, aux


def moe_apply(
    p: Dict,
    x,  # (B, S, D) or (T, D)
    *,
    top_k: int,
    activation: str = "swiglu",
    capacity_factor: float = 1.25,
    dispatch: str = "auto",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (output, aux_loss). dispatch: auto | capacity | a2a | dense."""
    if dispatch in ("auto", "a2a"):
        routed = moe_apply_a2a(p, x, top_k=top_k, activation=activation,
                               capacity_factor=capacity_factor)
        if routed is not None:
            out, aux = routed
            if "shared" in p:
                out = out + mlp_apply(p["shared"], x, activation)
            return out, aux
        if dispatch == "a2a":
            raise ValueError("a2a dispatch prerequisites not met")
        dispatch = "capacity"
    shape = x.shape
    d = shape[-1]
    xt = x.reshape(-1, d)
    t = xt.shape[0]
    e = p["w1"].shape[0]

    probs = router_probs(p, xt)  # (T, E) f32
    top_w, top_i = jax.lax.top_k(probs, top_k)  # (T, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    aux = load_balance_loss(probs, top_i, e)

    if dispatch == "dense":
        gates = jnp.zeros((t, e), jnp.float32)
        gates = gates.at[jnp.arange(t)[:, None], top_i].set(top_w)
        h = jnp.einsum("td,edf->tef", xt, p["w1"])
        h = _act(activation)(h) * jnp.einsum("td,edf->tef", xt, p["w3"])
        y = jnp.einsum("tef,efd->ted", h, p["w2"])
        out = jnp.einsum("ted,te->td", y, gates.astype(y.dtype))
        out = out.reshape(shape)
    else:
        # Row-wise (per-sequence) capacity dispatch: every op below is
        # batched over the (sharded) batch axis — no global sort, so GSPMD
        # never gathers the full token set. Expert blocks are (B, E, C, D)
        # with B on fsdp and E on the model axis.
        bsz = shape[0] if len(shape) == 3 else 1
        s = t // bsz
        xb = xt.reshape(bsz, s, d)
        k = top_k
        sk = s * k
        cap = _capacity(s, k, e, capacity_factor)
        flat_e = top_i.reshape(bsz, sk)
        flat_w = top_w.reshape(bsz, sk)
        order = jnp.argsort(flat_e, axis=1)
        sorted_e = jnp.take_along_axis(flat_e, order, axis=1)
        # position within each expert's block: index − first occurrence
        first = jax.vmap(lambda a: jnp.searchsorted(a, a, side="left"))(sorted_e)
        pos = jnp.arange(sk)[None, :] - first
        keep = pos < cap
        dest = jnp.where(keep, sorted_e * cap + pos, e * cap)  # overflow slot
        tok = order // k  # (B, Sk) source token within the row
        xg = jnp.take_along_axis(xb, tok[..., None], axis=1)  # (B, Sk, D)
        buf = jnp.zeros((bsz, e * cap + 1, d), x.dtype)
        buf = jax.vmap(lambda b, dd, v: b.at[dd].set(v))(buf, dest, xg)
        blocks = buf[:, : e * cap].reshape(bsz, e, cap, d)
        blocks = constrain_expert_batched(blocks)
        h = jnp.einsum("becd,edf->becf", blocks, p["w1"])
        h = _act(activation)(h) * jnp.einsum("becd,edf->becf", blocks, p["w3"])
        y = jnp.einsum("becf,efd->becd", h, p["w2"]).reshape(bsz, e * cap, d)
        y = jnp.concatenate([y, jnp.zeros((bsz, 1, d), y.dtype)], axis=1)
        gathered = jnp.take_along_axis(y, dest[..., None], axis=1)  # (B, Sk, D)
        w_sorted = (jnp.take_along_axis(flat_w, order, axis=1) * keep).astype(y.dtype)
        contrib = gathered * w_sorted[..., None]
        out = jnp.zeros((bsz, s, d), x.dtype)
        out = jax.vmap(lambda o, tt, c: o.at[tt].add(c))(out, tok, contrib)
        out = out.reshape(shape)

    if "shared" in p:
        out = out + mlp_apply(p["shared"], x, activation)
    return out, aux
