"""Mixture-of-Experts FFN: one router and two routed-expert layers, behind
one block that the LM calls without knowing which layer it runs.

:func:`route_topk` routes every token to ``top_k`` of ``n_experts`` for
both layers, with one of three routers: ``softmax``, ``sigmoid_bias``
(DeepSeek's aux-loss-free routing: a learned bias shifts the choice only)
and ``noaux_tc`` (DeepSeek-V3's group-limited form of it).

:func:`moe_apply`, the capacity layer, holds every expert, each with a
static capacity per token group (GShard); the dispatch's (group <->
expert) transpose is the all-to-all of expert parallelism, experts
sharded over `model`, tokens over `data`. It is the trainable path: the
held layer's ``moe_gmm`` kernel has no backward pass.

:func:`held_apply`, the held layer (``ep_size`` set), is one rank's share
of an expert-parallel deployment: it holds experts ``ep_rank * n_held ..
(ep_rank + 1) * n_held - 1`` of ``n_experts``, routes over all of them,
and computes, without dropping a token, only what its own experts add,
through the ``moe_gmm`` grouped-matmul kernel; the shared expert is added
once. The exchange with the other ranks is not part of the layer.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.kernels.moe_gmm import moe_gmm

from .common import PRec, constrain, rms_norm
from .mlp import mlp_apply, mlp_recs

#: tokens a held-expert layer routes and computes at a time: its buffers
#: are sized for the worst case, every token's pairs held here
HELD_CHUNK = 1024
#: the held experts' matrices, which the grouped matmul reads in place
EXPERT_WEIGHTS = ("w_gate", "w_up", "w_out")


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    n_shared: int = 0
    router: str = "softmax"     # 'softmax' | 'sigmoid_bias' (aux-loss-free)
    #                             | 'noaux_tc' (DeepSeek-V3 group-limited)
    capacity_factor: float = 1.25
    router_dtype: str = "float32"
    n_group: int = 1            # noaux_tc: expert groups, top groups kept
    topk_group: int = 1
    routed_scale: float = 1.0   # noaux_tc: routed_scaling_factor
    ep_size: int = 0            # 0: hold every expert (capacity path);
    ep_rank: int = 0            # else hold n_experts / ep_size, dropless

    @property
    def n_held(self) -> int:
        """Experts this layer holds."""
        return self.n_experts // self.ep_size if self.ep_size else \
            self.n_experts


def moe_recs(cfg) -> dict[str, PRec]:
    m: MoEConfig = cfg.moe
    d, ff, e = cfg.d_model, m.d_ff_expert, m.n_held
    recs = {
        "router": PRec((d, m.n_experts), ("embed", None),
                       dtype=jnp.float32),
        # EP: experts shard over `model`, so the per-expert ff dim stays
        # unsharded (experts and ff cannot both map to the model axis)
        "w_gate": PRec((e, d, ff), ("experts", "embed", "eff")),
        "w_up": PRec((e, d, ff), ("experts", "embed", "eff")),
        "w_out": PRec((e, ff, d), ("experts", "eff", "embed"),
                      scale=ff ** -0.5),
        "ln": PRec((d,), ("embed",), init="zeros"),
    }
    if m.router in ("sigmoid_bias", "noaux_tc"):
        recs["router_bias"] = PRec((m.n_experts,), (None,), init="zeros",
                                   dtype=jnp.float32)
    if m.n_shared:
        recs["shared"] = mlp_recs(cfg, d_ff=m.n_shared * ff)
    return recs


def moe_cache_recs(cfg) -> dict[str, PRec]:
    """A block's cache: for a held layer, the held experts' pairs and
    active experts, summed over the calls that wrote it; nothing for a
    capacity layer."""
    if not cfg.moe.ep_size:
        return {}
    return {"moe_stats": PRec((2,), (None,), dtype=jnp.int32, init="zeros")}


def scan_stacked(cfg) -> tuple[str, ...]:
    """The block's weights that stay stacked outside the layer scan: a held
    layer's kernel reads layer ``i`` where it lies, where a per-layer slice
    would be copied whole for the custom call."""
    return EXPERT_WEIGHTS if cfg.moe.ep_size else ()


def block_apply(p, x, cfg, rule=None, cache=None, layer=None):
    """The MoE block, x: (b, s, d): ``(what it adds to x, cache)``. A held
    layer adds its counts to the cache's; a capacity layer's cache is
    empty and passes through."""
    if not cfg.moe.ep_size:
        return moe_apply(p, x, cfg, rule=rule), cache
    dx, stats = held_apply(p, x, cfg, layer=layer)
    if cache is not None:
        cache = {"moe_stats": cache["moe_stats"] + stats}
    return dx, cache


def held_counts(cfg):
    """The function from a model's caches to its held experts' pairs and
    active experts, (2,) int32, summed over every held layer and the calls
    that wrote the caches; None when no layer holds a share."""
    if not (cfg.moe and cfg.moe.ep_size):
        return None

    def totals(caches):
        leaves = [leaf for path, leaf in
                  jax.tree_util.tree_flatten_with_path(caches)[0]
                  if getattr(path[-1], "key", None) == "moe_stats"]
        return sum(leaf.reshape(-1, 2).sum(0) for leaf in leaves)
    return totals


def _ffn(p, x, cfg, routed, rule=None):
    """A routed-expert FFN on x: (b, s, d). ``routed`` takes the normed
    tokens, (t, d), and returns what the routed experts add, (t, d), and a
    count of its own; the shared expert is added to the first. Returns
    ``(out (b, s, d), count)``."""
    b, s, d = x.shape
    out, count = routed(rms_norm(x, p["ln"]).reshape(b * s, d))
    out = out.reshape(b, s, d)
    if cfg.moe.n_shared:
        out = out + mlp_apply(p["shared"], x, cfg, rule=rule)
    if rule is not None:
        out = constrain(out, rule, ("batch", "seq", "act_embed"))
    return out, count


def route_topk(p, xt, m: MoEConfig):
    """Tokens ``xt`` (t, d) over all ``n_experts``: (experts (t, k) int32,
    combine weights (t, k) float32).

    ``softmax``: the ``top_k`` best logits, weighted by the softmax over
    all experts, not renormalised. ``sigmoid_bias``: ``s = sigmoid(x W)``;
    the ``top_k`` best ``s + bias`` are chosen (the bias shifts the choice
    only), weighted by their ``s`` normalised to sum 1. ``noaux_tc``: a
    group's score is the sum of its two best ``s + bias`` and only the
    ``topk_group`` best groups' experts compete; the choice and weights are
    then as for ``sigmoid_bias``, times ``routed_scale``. The router's
    product is computed in full float32, as its weights are stored: a TPU's
    default would round both operands to bfloat16 and flip near-tied
    choices."""
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"],
                        precision=jax.lax.Precision.HIGHEST)
    if m.router == "softmax":
        _, idx = jax.lax.top_k(logits, m.top_k)
        return idx, jnp.take_along_axis(jax.nn.softmax(logits, axis=-1),
                                        idx, axis=1)
    s = jax.nn.sigmoid(logits)
    sel = s + p["router_bias"]
    if m.router == "sigmoid_bias":
        _, idx = jax.lax.top_k(sel, m.top_k)
        w = jnp.take_along_axis(s, idx, axis=1)
        return idx, w / (w.sum(-1, keepdims=True) + 1e-9)
    if m.router != "noaux_tc":
        raise ValueError(f"unknown router {m.router!r}")
    t, e = sel.shape
    groups = sel.reshape(t, m.n_group, e // m.n_group)
    g_score = jax.lax.top_k(groups, 2)[0].sum(-1)               # (t, G)
    _, g_idx = jax.lax.top_k(g_score, m.topk_group)
    keep = jax.nn.one_hot(g_idx, m.n_group, dtype=jnp.int32).sum(1)
    keep = jnp.repeat(keep > 0, e // m.n_group, axis=1)        # (t, e)
    _, idx = jax.lax.top_k(jnp.where(keep, sel, -jnp.inf), m.top_k)
    w = jnp.take_along_axis(s, idx, axis=1)
    return idx, w / (w.sum(-1, keepdims=True) + 1e-20) * m.routed_scale


def moe_apply(p, x, cfg, rule=None):
    """The capacity layer, x: (b, s, d). Static-capacity top-k dispatch,
    canonical GShard group-local form: tokens are split into G groups (one
    per data shard, ``rule['moe_groups']``), routing positions and capacity
    are computed *within* the group, dispatch/combine scatters stay
    group-local, and the (group <-> expert) transpose between the dispatch
    buffer and the expert FFN is the one true all-to-all of expert
    parallelism. Dispatch and combine are scatter-add/gather in (token, k)
    pair space, free of matmuls: the classic one-hot einsum dispatch costs
    2·t_g·(e·c_g)·d ≈ 2.5·k·t_g²·d MXU flops per group, ~800x the useful
    expert compute at deepseek-v3 scale when G=1 (t=1M)."""
    out, _ = _ffn(p, x, cfg,
                  lambda xt: (_capacity_part(p, xt, cfg.moe, rule), None),
                  rule)
    return out


def _capacity_part(p, xt, m: MoEConfig, rule=None):
    """Tokens ``xt`` (t, d), normed: what the routed experts add, (t, d),
    each expert taking at most its capacity of a group's pairs."""
    t, d = xt.shape
    G = (rule or {}).get("moe_groups", 1)
    if t % G:
        G = 1
    tg = t // G
    idx, w = route_topk(p, xt, m)
    idx, w = idx.reshape(G, tg, m.top_k), w.reshape(G, tg, m.top_k)
    xt = xt.reshape(G, tg, d)

    # floor 8: tiny decode groups otherwise drop colliding tokens
    capacity = max(min(8, tg), int(m.capacity_factor * m.top_k * tg
                                   / m.n_experts))
    # position of each token within its expert's group-local buffer
    khot = jax.nn.one_hot(idx, m.n_experts, dtype=jnp.int32).sum(2)
    pos_te = jnp.cumsum(khot, axis=1) - khot                   # (G,tg,e)
    # scatter dispatch in (token, k) pair space; overflow pairs land in
    # the per-expert spill slot (index `capacity`), dropped afterwards
    pos_k = jnp.take_along_axis(pos_te, idx, axis=2)          # (G, tg, k)
    keep_k = pos_k < capacity
    pos_k = jnp.where(keep_k, pos_k, capacity)
    slot = idx * (capacity + 1) + pos_k                       # (G, tg, k)
    src = jnp.broadcast_to(xt[:, :, None, :], (G, tg, m.top_k, d))
    zeros = jnp.zeros((G, m.n_experts * (capacity + 1), d), xt.dtype)
    xin = jax.vmap(lambda z, sl, sr: z.at[sl].add(sr))(
        zeros, slot.reshape(G, tg * m.top_k),
        src.reshape(G, tg * m.top_k, d))
    xin = xin.reshape(G, m.n_experts, capacity + 1, d)[:, :, :capacity]

    # (G, e, c, d) -> (e, G, c, d): the EP all-to-all (groups live on the
    # data axis, experts on the model axis)
    xin = xin.swapaxes(0, 1)
    if rule is not None:
        xin = constrain(xin, rule, ("act_experts", "batch", None, None))
    gt = jnp.einsum("egcd,edf->egcf", xin, p["w_gate"])
    u = jnp.einsum("egcd,edf->egcf", xin, p["w_up"])
    h = jax.nn.silu(gt) * u
    eout = jnp.einsum("egcf,efd->egcd", h, p["w_out"])
    if rule is not None:
        eout = constrain(eout, rule, ("act_experts", "batch", None, None))
    eout = eout.swapaxes(0, 1)                                # a2a back

    eout = eout.astype(xt.dtype)    # combine in bf16: halves the a2a/AR wire
    pad = jnp.zeros((G, m.n_experts, 1, d), eout.dtype)
    flat = jnp.concatenate([eout, pad], axis=2) \
        .reshape(G, m.n_experts * (capacity + 1), d)
    gathered = jnp.take_along_axis(
        flat, slot.reshape(G, tg * m.top_k)[..., None], axis=1) \
        .reshape(G, tg, m.top_k, d)
    out = jnp.einsum("gtkd,gtk->gtd", gathered,
                     (w * keep_k).astype(xt.dtype))
    return out.reshape(t, d)


def _held_part(p, xt, m: MoEConfig, layer=None):
    """Tokens ``xt`` (t, d), normed: what the held experts add, (t, d), and
    the held experts' pair counts, (n_held,) int32."""
    t, d = xt.shape
    k = m.top_k
    with jax.named_scope("route"):
        idx, w = route_topk(p, xt, m)
    with jax.named_scope("dispatch"):
        local = idx - m.ep_rank * m.n_held
        held = (local >= 0) & (local < m.n_held)
        group = jnp.where(held, local, m.n_held).reshape(t * k)
        order = jnp.argsort(group, stable=True)         # held pairs first
        sizes = jax.nn.one_hot(group, m.n_held + 1,
                               dtype=jnp.int32).sum(0)[:m.n_held]
        rows = jnp.take(xt, order // k, axis=0)
    with jax.named_scope("gmm"):
        g = moe_gmm(rows, p["w_gate"], sizes, layer=layer)
        u = moe_gmm(rows, p["w_up"], sizes, layer=layer)
        h = (jax.nn.silu(g.astype(jnp.float32))
             * u.astype(jnp.float32)).astype(rows.dtype)
        y = moe_gmm(h, p["w_out"], sizes, layer=layer)  # 0 past the groups
    with jax.named_scope("combine"):
        back = jnp.argsort(order)                       # pair -> sorted row
        y = jnp.take(y, back, axis=0).reshape(t, k, d)
        out = jnp.einsum("tkd,tk->td", y.astype(jnp.float32),
                         jnp.where(held, w, 0.0))
    return out.astype(xt.dtype), sizes


def held_apply(p, x, cfg, layer=None):
    """One rank's share of an expert-parallel MoE layer, x: (b, s, d).

    Routes every token over all ``n_experts``, computes each pair whose
    expert is held here (none dropped) and adds the shared expert once.
    Returns ``(out, stats)``: ``stats`` (2,) int32 is the pairs the held
    experts computed and how many held experts got at least one. Calls of
    more than :data:`HELD_CHUNK` tokens go that many at a time. With
    ``layer``, ``p``'s :data:`EXPERT_WEIGHTS` are a layer stack and
    ``layer`` picks from it."""
    m: MoEConfig = cfg.moe

    def routed(xt):
        t, d = xt.shape
        c = HELD_CHUNK if t > HELD_CHUNK and t % HELD_CHUNK == 0 else t
        if c == t:
            return _held_part(p, xt, m, layer)
        out, sizes = jax.lax.map(lambda xc: _held_part(p, xc, m, layer),
                                 xt.reshape(t // c, c, d))
        return out.reshape(t, d), sizes.sum(0)

    out, sizes = _ffn(p, x, cfg, routed)
    stats = jnp.stack([sizes.sum(), (sizes > 0).sum()]).astype(jnp.int32)
    return out, stats
