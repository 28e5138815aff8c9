"""Mixture-of-Experts FFN: top-k router + expert-parallel execution.

Production path (``apply_moe`` with a mesh): the layer runs inside
``shard_map``. Expert weights are sharded over the ``model`` mesh axis;
activations arrive batch-sharded over (``pod``, ``data``) and replicated over
``model``. Each device routes its *local* tokens, gathers the ones assigned
to its *local* experts into a capacity-bounded (E_loc, C, d) group buffer,
runs the expert FFNs as dense MXU matmuls, scatter-adds weighted outputs to
a local partial, and a single ``psum`` over ``model`` combines expert
contributions — the same one collective a Megatron-sharded dense FFN needs.
No all-to-all and no (B,T,E,C) dispatch tensor is ever materialized.

Reference path (``apply_moe_dense``): the naive every-expert-sees-every-token
einsum. Exact, O(E/k) more FLOPs — used as the oracle in tests and for tiny
smoke configs only.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import common


def init_moe(cfg: ArchConfig, rng) -> dict:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(rng, 4)
    return {
        "router": common.normal_init(ks[0], (d, E), 0.02),
        "w_gate": common.he_init(ks[1], (E, d, f), d),
        "w_up": common.he_init(ks[2], (E, d, f), d),
        "w_down": common.he_init(ks[3], (E, f, d), f),
    }


def logical_axes(cfg: ArchConfig) -> dict:
    return {
        "router": ("embed", None),
        "w_gate": ("experts", "embed", "expert_ffn"),
        "w_up": ("experts", "embed", "expert_ffn"),
        "w_down": ("experts", "expert_ffn", "embed"),
    }


def _route(router_w, x, cfg: ArchConfig):
    """x (N,d) -> (topv (N,k) f32 renormalized, topi (N,k) i32, aux scalar)."""
    logits = (x @ router_w.astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, cfg.top_k)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    # Switch-style load-balance loss over the local token set
    frac_tokens = jnp.mean(
        jax.nn.one_hot(topi, cfg.n_experts, dtype=jnp.float32), axis=(0, 1))
    frac_probs = jnp.mean(probs, axis=0)
    aux = cfg.n_experts * jnp.sum(frac_tokens * frac_probs)
    return topv, topi, aux


def _expert_ffn(p, xe, cfg: ArchConfig, e_slice=None):
    """xe (E?, C, d) against expert weight stacks (E?, d, f)."""
    dt = xe.dtype
    wg, wu, wd = p["w_gate"], p["w_up"], p["w_down"]
    if e_slice is not None:
        wg, wu, wd = wg[e_slice], wu[e_slice], wd[e_slice]
    g = jnp.einsum("ecd,edf->ecf", xe, wg.astype(dt))
    u = jnp.einsum("ecd,edf->ecf", xe, wu.astype(dt))
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, wd.astype(dt))


def _capacity(n_tokens: int, cfg: ArchConfig, n_local_experts: int,
              factor: float = None) -> int:
    factor = factor if factor is not None else cfg.moe_capacity_factor
    expect = n_tokens * cfg.top_k / cfg.n_experts
    c = int(factor * expect) + 8
    return max(8, (c + 7) // 8 * 8)


def _moe_local(p_local, x, cfg: ArchConfig, e_offset, n_local_experts: int,
               capacity: int):
    """Grouped dispatch over the device-local token set and expert shard.

    p_local: expert weights already sliced to the local shard (E_loc, ...).
    x: (N, d) local tokens. e_offset: global id of first local expert.
    Returns (y_partial (N, d) — contributions of LOCAL experts only, aux).
    """
    N, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    dt = x.dtype
    topv, topi, aux = _route(p_local["router"], x, cfg)

    # map global expert ids to local slots; non-local -> capacity overflow bin
    local_e = topi - e_offset                                   # (N,k)
    is_local = (local_e >= 0) & (local_e < n_local_experts)
    flat_e = jnp.where(is_local, local_e, n_local_experts).reshape(-1)  # (N*k,)

    # position of each (token, slot) in its expert queue (stable order)
    onehot = jax.nn.one_hot(flat_e, n_local_experts + 1, dtype=jnp.int32)
    pos_in_e = jnp.cumsum(onehot, axis=0) - onehot
    slot = jnp.sum(pos_in_e * onehot, axis=1)                   # (N*k,)
    keep = (slot < capacity) & (flat_e < n_local_experts)
    dest = jnp.where(keep, flat_e * capacity + slot,
                     n_local_experts * capacity)

    # Dispatch/combine unrolled over the k routing slots: a single fused
    # gather would materialize an (N*k, d) tensor — measured 4 GiB (+4 GiB
    # f32 cotangent) per layer at qwen3 scale (§Perf D). Per-slot scatters
    # touch only (N, d) at a time.
    dest2 = dest.reshape(N, k)
    buf = jnp.zeros((n_local_experts * capacity + 1, d), dt)
    for j in range(k):
        buf = buf.at[dest2[:, j]].set(x, mode="drop")
    xe = buf[:-1].reshape(n_local_experts, capacity, d)

    ye = _expert_ffn(p_local, xe, cfg)                          # (E_loc,C,d)

    yf = ye.reshape(n_local_experts * capacity, d)
    w2 = (topv * keep.reshape(N, k)).astype(dt)                 # (N,k)
    src2 = jnp.minimum(dest2, n_local_experts * capacity - 1)
    y = jnp.zeros((N, d), dt)
    for j in range(k):
        y = y + yf[src2[:, j]] * w2[:, j, None]
    return y, aux


def apply_moe(p, x, cfg: ArchConfig, mesh: Optional[Mesh] = None,
              expert_axis: str = "model"):
    """x (B,T,d) -> (y (B,T,d), aux). Expert-parallel when a mesh with the
    expert axis is provided; single-device grouped dispatch otherwise."""
    B, T, d = x.shape

    if mesh is None or expert_axis not in mesh.shape:
        xf = x.reshape(B * T, d)
        cap = _capacity(B * T, cfg, cfg.n_experts)
        y, aux = _moe_local(p, xf, cfg, 0, cfg.n_experts, cap)
        return y.reshape(B, T, d), aux

    n_shards = mesh.shape[expert_axis]
    assert cfg.n_experts % n_shards == 0, (cfg.n_experts, n_shards)
    e_loc = cfg.n_experts // n_shards
    # shard the batch over whichever data-like axes divide it (B=1 decode
    # shapes leave the data axes idle)
    batch_axes = []
    prod = 1
    for a in ("pod", "data"):
        if a in mesh.shape and B % (prod * mesh.shape[a]) == 0:
            batch_axes.append(a)
            prod *= mesh.shape[a]
    batch_axes = tuple(batch_axes)

    # FSDP composition: expert weights stay sharded over `data` on their
    # embed/ffn dims in the in_specs and are all-gathered INSIDE the body —
    # when this layer runs under scan-over-layers that keeps the gather
    # per-layer-per-step. Replicated in_specs instead would force XLA to
    # materialize the full 48-layer expert stack before the scan
    # (measured: +10 GiB temp on qwen3-moe train_4k; §Perf D).
    fsdp = ("data" in mesh.shape and cfg.d_model % mesh.shape["data"] == 0
            and cfg.d_ff % 1 == 0)
    fsdp_axis = "data" if fsdp else None

    def shard_fn(p_sh, x_sh):
        # x_sh: (B_loc, T, d) — replicated over the expert axis
        if fsdp_axis is not None:
            p_sh = dict(
                p_sh,
                w_gate=jax.lax.all_gather(p_sh["w_gate"], fsdp_axis,
                                          axis=1, tiled=True),
                w_up=jax.lax.all_gather(p_sh["w_up"], fsdp_axis,
                                        axis=1, tiled=True),
                w_down=jax.lax.all_gather(p_sh["w_down"], fsdp_axis,
                                          axis=2, tiled=True),
            )
        Bl, Tl, dl = x_sh.shape
        eid = jax.lax.axis_index(expert_axis)
        cap = _capacity(Bl * Tl, cfg, e_loc)
        y, aux = _moe_local(p_sh, x_sh.reshape(Bl * Tl, dl), cfg,
                            eid * e_loc, e_loc, cap)
        y = jax.lax.psum(y, expert_axis)          # combine expert partials
        aux = jax.lax.pmean(aux, expert_axis)
        if batch_axes:
            aux = jax.lax.pmean(aux, batch_axes)
        return y.reshape(Bl, Tl, dl), aux

    if fsdp_axis is not None:
        wspec = {"w_gate": P(expert_axis, fsdp_axis, None),
                 "w_up": P(expert_axis, fsdp_axis, None),
                 "w_down": P(expert_axis, None, fsdp_axis)}
    else:
        wspec = {"w_gate": P(expert_axis), "w_up": P(expert_axis),
                 "w_down": P(expert_axis)}
    pspec = {"router": P(), **wspec}
    xspec = P(batch_axes if batch_axes else None)
    fn = jax.shard_map(shard_fn, mesh=mesh,
                       in_specs=(pspec, xspec),
                       out_specs=(xspec, P()),
                       check_vma=False)
    return fn(p, x)


def apply_moe_dense(p, x, cfg: ArchConfig):
    """Oracle: every expert computes every token; combine by router weights."""
    B, T, d = x.shape
    E = cfg.n_experts
    dt = x.dtype
    topv, topi, aux = _route(p["router"], x.reshape(B * T, d), cfg)
    combine = jnp.sum(jax.nn.one_hot(topi, E, dtype=jnp.float32)
                      * topv[..., None], axis=1)                # (N,E)
    xf = x.reshape(1, B * T, d) * jnp.ones((E, 1, 1), dt)
    ye = _expert_ffn(p, xf, cfg)                                # (E,N,d)
    y = jnp.einsum("end,ne->nd", ye.astype(jnp.float32),
                   combine).astype(dt)
    return y.reshape(B, T, d), aux
