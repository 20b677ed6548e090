"""Mixture-of-Experts with sort-based capacity dispatch (TPU-native).

Expert FFNs *are* sparse parameters in the Parallax sense — each token
touches k of E experts (α = k/E) with learned indices — so the same
Table-3-style reasoning that picks the embedding exchange picks the MoE
execution plan:

  ep  experts sharded over ``model`` (E/M per chip); tokens routed to owners
      via all_to_all — the PS push/pull pattern applied to activations.
      Used when E % M == 0 (llama4-maverick: 128 experts / 16 shards).
  tp  experts replicated over ``model`` with expert d_ff sharded; dispatch is
      device-local and expert outputs are psum'd. Used when E < M (grok-1: 8
      experts), where EP cannot divide.

Dispatch is sort-based (argsort by expert id + positional capacity), not
GShard one-hot-einsum — O(T·D + E·C·D) memory instead of O(T·E·C). Tokens
are processed in groups (scan) to bound the dispatch buffers.

A config with ``experts_held`` > 0 takes the held-experts layer instead
(``held_moe_ffn``): one device's share of an expert-parallel layer. It
routes over all ``n_experts`` and computes only the experts it holds,
dropless: the (token, slot) pairs routed here are sorted by expert and go
through grouped products (``lax.ragged_dot``) over exactly those rows;
pairs routed to absent experts contribute nothing. No capacity, no padding
to E×C, no all-to-all (the exchange between shares is not built yet).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.compat import P, shard_map
from repro.models.layers import ParamSpec, swiglu
from repro.utils.hlo import MOE_EXPERTS, MOE_ROUTE, MOE_SHARED


def moe_specs(cfg, exec_mode: str) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    e = cfg.experts_held or cfg.n_experts
    if exec_mode == "ep":
        axes_in = ("experts", None, None)
        axes_out = ("experts", None, None)
    else:
        axes_in = (None, None, "mlp")
        axes_out = (None, "mlp", None)
    specs = {
        "router": ParamSpec((d, cfg.n_experts), (None, None), scale=0.02),
        "w_gate": ParamSpec((e, d, f), axes_in, fan_in_axes=(1,)),
        "w_up": ParamSpec((e, d, f), axes_in, fan_in_axes=(1,)),
        "w_down": ParamSpec((e, f, d), axes_out, fan_in_axes=(1,)),
    }
    if cfg.shared_expert:
        sf = cfg.shared_d_ff or f
        specs["shared_gate"] = ParamSpec((d, sf), (None, "mlp"), fan_in_axes=(0,))
        specs["shared_up"] = ParamSpec((d, sf), (None, "mlp"), fan_in_axes=(0,))
        specs["shared_down"] = ParamSpec((sf, d), ("mlp", None), fan_in_axes=(0,))
    return specs


def route(flat, router_w, cfg):
    """Router over all ``n_experts`` in float32 (DeepSeek-V3's gate):
    sigmoid scores, top-k, the chosen weights normalised to sum to one and
    scaled by ``routed_scale``. -> weights (T,k) f32, ids (T,k)."""
    logits = jnp.matmul(flat.astype(jnp.float32),
                        router_w.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    w, eids = jax.lax.top_k(jax.nn.sigmoid(logits), cfg.experts_per_token)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * cfg.routed_scale, eids


@jax.custom_vjp
def _permute(x, perm, inv):
    """``x[perm]`` for a permutation ``perm`` whose inverse is ``inv``: the
    backward is the gather ``g[inv]``, not the scatter-add a gather's
    transpose is in general."""
    return x[perm]


def _permute_fwd(x, perm, inv):
    return x[perm], inv


def _permute_bwd(inv, g):
    return g[inv], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def held_moe_ffn(params: dict, x: jax.Array, *, cfg, rt,
                 first_expert: int = 0):
    """x: (B, S, D) -> (B, S, D), metrics: the routed experts
    ``first_expert`` .. ``first_expert + experts_held`` (the leaves'
    experts, in order) for the tokens routed to them, plus the shared
    experts in full.

    Counters: ``moe_held_rows`` (token, slot) pairs computed here;
    ``moe_load_max`` the busiest held expert's rows over the mean held
    expert's; ``moe_dropped`` 0, since nothing is dropped."""
    b, s, d = x.shape
    k, held = cfg.experts_per_token, cfg.experts_held
    flat = x.reshape(b * s, d)
    with jax.named_scope(MOE_ROUTE):
        w, eids = route(flat, params["router"], cfg)
        local = eids - first_expert
        here = (local >= 0) & (local < held)
        # absent experts sort last, as group ``held``, which no product reads
        slot_e = jnp.where(here, local, held).reshape(-1)
        order = jnp.argsort(slot_e, stable=True)
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        sizes = jnp.sum(slot_e[:, None] == jnp.arange(held), axis=0,
                        dtype=jnp.int32)
        n_here = jnp.sum(sizes)
        # ragged_dot leaves the rows past its last group undefined (on the
        # TPU, in its backward too): they are zeroed before each product
        # reads them, so no pass carries them into a gradient
        valid = (jnp.arange(order.shape[0]) < n_here)[:, None]
        rows = jnp.where(valid, _permute(jnp.repeat(flat, k, axis=0), order,
                                         inv), 0).astype(rt.dtype)
    with jax.named_scope(MOE_EXPERTS):
        def gmm(a, w_):
            return jax.lax.ragged_dot(a, w_.astype(rt.dtype), sizes)
        h = jnp.where(valid, jax.nn.silu(gmm(rows, params["w_gate"]))
                      * gmm(rows, params["w_up"]), 0)
        ys = gmm(h, params["w_down"])
    with jax.named_scope(MOE_ROUTE):
        picked = jnp.where(here[..., None],
                           _permute(ys, inv, order).reshape(b * s, k, d), 0)
        out = jnp.sum((picked * w[..., None].astype(picked.dtype))
                      .astype(jnp.float32), axis=1).astype(x.dtype)
        out = out.reshape(b, s, d)
    metrics = {"moe_dropped": jnp.int32(0), "moe_held_rows": n_here,
               "moe_load_max": (jnp.max(sizes) * held).astype(jnp.float32)
               / jnp.maximum(n_here, 1).astype(jnp.float32)}
    if cfg.shared_expert:
        out = out + _shared(params, x, rt).astype(out.dtype)
    return out, metrics


@jax.named_scope(MOE_SHARED)
def _shared(params, x, rt):
    from repro.core import sp
    if sp.sp_active(rt, x):
        g, u = sp.proj_in(rt, x, [params["shared_gate"],
                                  params["shared_up"]], [True, True])
        return sp.proj_out(rt, jax.nn.silu(g) * u, params["shared_down"])
    return swiglu(x, params["shared_gate"], params["shared_up"],
                  params["shared_down"],
                  constrain=lambda h, _: rt.constrain(h, ("batch", None, "mlp")))


def _dispatch_indices(eids, gates, n_experts, capacity):
    """Sort-based dispatch. eids/gates: (T, k).

    Returns (slot_dest (T,k) flat index into E*C+1 buffer [E*C = dropped],
             aux metrics).
    """
    t, k = eids.shape
    flat_e = eids.reshape(-1)                                  # (T*k,)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    # position of each routed slot within its expert
    start = jnp.searchsorted(sorted_e, jnp.arange(n_experts), side="left")
    pos = jnp.arange(t * k) - start[sorted_e]
    keep = pos < capacity
    dest_sorted = jnp.where(keep, sorted_e * capacity + pos, n_experts * capacity)
    # scatter back to slot order
    dest = jnp.zeros((t * k,), jnp.int32).at[order].set(dest_sorted.astype(jnp.int32))
    dropped = jnp.sum(~keep).astype(jnp.int32)
    return dest.reshape(t, k), dropped


def _expert_ffn(xs, w_gate, w_up, w_down, compute_dtype):
    """xs: (E, C, D); w: (E, D, F) / (E, F, D)."""
    xs = xs.astype(compute_dtype)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xs, w_gate.astype(compute_dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", xs, w_up.astype(compute_dtype))
    return jnp.einsum("ecf,efd->ecd", h, w_down.astype(compute_dtype))


def _moe_group(flat, router_w, w_gate, w_up, w_down, *, e, k, cf,
               exec_mode, model_axis, m, compute_dtype):
    """One token group on one device. flat: (T, D)."""
    t, d = flat.shape
    cap = max(int(t * k * cf / e) + 1, 4)
    logits = (flat @ router_w.astype(flat.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eids = jax.lax.top_k(probs, k)                      # (T,k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    dest, dropped = _dispatch_indices(eids, gates, e, cap)

    buf = jnp.zeros((e * cap + 1, d), flat.dtype)
    xs = buf.at[dest.reshape(-1)].add(
        jnp.repeat(flat, k, axis=0), mode="drop")[:-1]
    xs = xs.reshape(e, cap, d)

    if exec_mode == "ep" and m > 1:
        e_loc = e // m
        xs = xs.reshape(m, e_loc, cap, d)
        xs = jax.lax.all_to_all(xs, model_axis, split_axis=0, concat_axis=0)
        # (M, E_loc, C, D): peer-m's tokens for my experts
        xs = xs.transpose(1, 0, 2, 3).reshape(e_loc, m * cap, d)
        ys = _expert_ffn(xs, w_gate, w_up, w_down, compute_dtype)
        ys = ys.reshape(e_loc, m, cap, d).transpose(1, 0, 2, 3)
        ys = jax.lax.all_to_all(ys, model_axis, split_axis=0, concat_axis=0)
        ys = ys.reshape(e, cap, d)
    else:
        ys = _expert_ffn(xs, w_gate, w_up, w_down, compute_dtype)
        if exec_mode == "tp" and m > 1:
            ys = jax.lax.psum(ys, model_axis)

    ys_pad = jnp.concatenate(
        [ys.reshape(e * cap, d), jnp.zeros((1, d), ys.dtype)], axis=0)
    picked = ys_pad[dest.reshape(-1)].reshape(t, k, d)
    out = jnp.sum(picked * gates[..., None].astype(picked.dtype), axis=1)

    # GShard load-balance aux (top-1 fraction x mean prob)
    frac = jnp.mean(jax.nn.one_hot(eids[:, 0], e, dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(frac * jnp.mean(probs, axis=0))
    return out.astype(flat.dtype), aux, dropped


def moe_ffn(params: dict, x: jax.Array, *, cfg, rt, exec_mode: str,
            group_tokens: int = 8192):
    """x: (B, S, D) -> (B, S, D), metrics."""
    if cfg.experts_held:
        return held_moe_ffn(params, x, cfg=cfg, rt=rt)
    b, s, d = x.shape
    e, k, cf = cfg.n_experts, cfg.experts_per_token, cfg.moe_capacity_factor
    mesh = rt.mesh
    model_axis = "model" if (mesh and "model" in mesh.axis_names) else None
    m = mesh.shape[model_axis] if model_axis else 1
    batch_axes = rt.batch_axes or None
    if exec_mode == "ep" and (m <= 1 or e % m != 0):
        exec_mode = "tp"
    seq_shardable = model_axis is not None and m > 1 and s % m == 0 \
        and exec_mode == "ep"

    def local(x_loc, router_w, w_gate, w_up, w_down):
        bl, sl, _ = x_loc.shape
        flat = x_loc.reshape(bl * sl, d)
        t = flat.shape[0]
        g = max(min(group_tokens, t), 1)
        n_groups = (t + g - 1) // g
        if t % g != 0:
            flat = jnp.pad(flat, ((0, n_groups * g - t), (0, 0)))

        def run_group(fl):
            return _moe_group(
                fl, router_w, w_gate, w_up, w_down, e=e, k=k, cf=cf,
                exec_mode=exec_mode, model_axis=model_axis, m=m,
                compute_dtype=rt.dtype)

        if n_groups == 1:
            out, aux, dropped = run_group(flat)
        else:
            outs, auxs, drops = jax.lax.map(
                run_group, flat.reshape(n_groups, g, d))
            out = outs.reshape(n_groups * g, d)
            aux, dropped = jnp.mean(auxs), jnp.sum(drops)
        out = out[:t].reshape(bl, sl, d)
        if mesh is not None:
            token_axes = tuple(a for a in (batch_axes or ())) + \
                ((model_axis,) if seq_shardable else ())
            if token_axes:
                n = 1
                for a in token_axes:
                    n *= mesh.shape[a]
                aux = jax.lax.psum(aux, token_axes) / n
                dropped = jax.lax.psum(dropped, token_axes)
            if not seq_shardable and model_axis and m > 1 and exec_mode == "ep":
                # tokens replicated over model: aux already identical
                pass
        return out, aux, dropped

    if mesh is None:
        out, aux, dropped = local(x, params["router"], params["w_gate"],
                                  params["w_up"], params["w_down"])
    else:
        seq_spec = model_axis if seq_shardable else None
        if exec_mode == "ep":
            wspec = P(model_axis, None, None)
            wspec_down = P(model_axis, None, None)
        else:
            wspec = P(None, None, model_axis)
            wspec_down = P(None, model_axis, None)
        fn = shard_map(
            local, mesh=mesh,
            in_specs=(P(batch_axes, seq_spec, None), P(), wspec, wspec, wspec_down),
            out_specs=(P(batch_axes, seq_spec, None), P(), P()),
            check_vma=False,
        )
        out, aux, dropped = fn(x, params["router"], params["w_gate"],
                               params["w_up"], params["w_down"])

    metrics = {"moe_aux": aux, "moe_dropped": dropped}
    if cfg.shared_expert:
        out = out + _shared(params, x, rt).astype(out.dtype)
    return out, metrics


def pick_exec_mode(cfg, rt) -> str:
    if rt.run_cfg.moe_exec in ("ep", "tp"):
        return rt.run_cfg.moe_exec
    m = rt.rules.axis_size("experts")
    if m > 1 and cfg.n_experts % m == 0:
        return "ep"
    return "tp"
