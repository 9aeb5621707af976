"""Mixture-of-Experts FFN: top-k routing with static capacity (GShard-style
one-hot dispatch → XLA all-to-all under expert parallelism), shared experts,
and DeepSeek-V3's aux-loss-free sigmoid routing with a learned bias.

Experts are sharded over the `model` axis (EP); the dispatch/combine einsums
contract the token dim (sharded over `data`), which XLA lowers to the
canonical all-to-all + all-reduce pattern of expert parallelism.

A layer with ``ep_size`` set is one rank's share of an expert-parallel
deployment (:func:`held_apply`): it holds experts ``ep_rank * n_held ..
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


def _topk_mask(scores, k):
    """scores: (T, E) -> (weights (T,E), mask (T,E))  [k-hot]"""
    vals, idx = jax.lax.top_k(scores, k)
    mask = jax.nn.one_hot(idx, scores.shape[-1], dtype=scores.dtype).sum(1)
    return mask


def _route(p, xt, m: MoEConfig):
    """Router: returns (weights (t, e), khot (t, e), idx (t, k))."""
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"])
    if m.router == "sigmoid_bias":
        # DeepSeek aux-loss-free: bias only affects selection, not weights
        sel_scores = jax.nn.sigmoid(logits) + p["router_bias"]
        gate_scores = jax.nn.sigmoid(logits)
    else:
        sel_scores = logits
        gate_scores = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(sel_scores, m.top_k)
    khot = jax.nn.one_hot(idx, m.n_experts,
                          dtype=gate_scores.dtype).sum(1)    # (t, e)
    weights = gate_scores * khot
    if m.router == "sigmoid_bias":                            # renormalize
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-9)
    return weights, khot, idx


def moe_apply(p, x, cfg, rule=None, dispatch: str = "scatter"):
    """x: (b, s, d). Static-capacity top-k dispatch, canonical GShard
    group-local form: tokens are split into G groups (one per data shard,
    ``rule['moe_groups']``), routing positions and capacity are computed
    *within* the group, dispatch/combine scatters stay group-local, and the
    (group <-> expert) transpose between the dispatch buffer and the expert
    FFN is the one true all-to-all of expert parallelism.

    dispatch='scatter' (default): matmul-free dispatch/combine via
    scatter-add/gather in (token, k) pair space. The classic one-hot einsum
    dispatch costs 2·t_g·(e·c_g)·d ≈ 2.5·k·t_g²·d MXU flops per group —
    ~800x the useful expert compute at deepseek-v3 scale when G=1 (t=1M);
    it is kept (dispatch='einsum') for small configs and the equivalence
    test (the two paths are numerically identical).
    """
    m: MoEConfig = cfg.moe
    b, s, d = x.shape
    xn = rms_norm(x, p["ln"])
    t = b * s
    G = (rule or {}).get("moe_groups", 1)
    if t % G:
        G = 1
    tg = t // G
    xt = xn.reshape(G, tg, d)
    weights, khot, idx = _route(p, xt.reshape(t, d), m)
    weights = weights.reshape(G, tg, m.n_experts)
    khot = khot.reshape(G, tg, m.n_experts)
    idx = idx.reshape(G, tg, m.top_k)

    # floor 8: tiny decode groups otherwise drop colliding tokens
    capacity = max(min(8, tg), int(m.capacity_factor * m.top_k * tg
                                   / m.n_experts))
    # position of each token within its expert's group-local buffer
    pos_te = (jnp.cumsum(khot, axis=1) - khot).astype(jnp.int32)  # (G,tg,e)

    if dispatch == "einsum":
        keep = (pos_te < capacity) & (khot > 0)
        disp = jax.nn.one_hot(jnp.where(keep, pos_te, capacity),
                              capacity, dtype=x.dtype)        # (G,tg,e,c)
        comb = disp * weights.astype(x.dtype)[..., None]
        xin = jnp.einsum("gtec,gtd->gecd", disp, xt)
    else:
        # scatter dispatch in (token, k) pair space; overflow pairs land in
        # the per-expert spill slot (index `capacity`), dropped afterwards
        pos_k = jnp.take_along_axis(pos_te, idx, axis=2)      # (G, tg, k)
        keep_k = pos_k < capacity
        pos_k = jnp.where(keep_k, pos_k, capacity)
        slot = idx * (capacity + 1) + pos_k                   # (G, tg, k)
        src = jnp.broadcast_to(xt[:, :, None, :], (G, tg, m.top_k, d))
        zeros = jnp.zeros((G, m.n_experts * (capacity + 1), d), x.dtype)
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

    eout = eout.astype(x.dtype)     # combine in bf16: halves the a2a/AR wire
    if dispatch == "einsum":
        out = jnp.einsum("gecd,gtec->gtd", eout, comb)
    else:
        pad = jnp.zeros((G, m.n_experts, 1, d), eout.dtype)
        flat = jnp.concatenate([eout, pad], axis=2) \
            .reshape(G, m.n_experts * (capacity + 1), d)
        gathered = jnp.take_along_axis(
            flat, slot.reshape(G, tg * m.top_k)[..., None], axis=1) \
            .reshape(G, tg, m.top_k, d)
        w_k = (jnp.take_along_axis(weights, idx, axis=2)
               * keep_k).astype(x.dtype)                      # (G, tg, k)
        out = jnp.einsum("gtkd,gtk->gtd", gathered, w_k)
    out = out.reshape(b, s, d)

    if m.n_shared:
        out = out + mlp_apply(p["shared"], x, cfg, rule=rule)
    if rule is not None:
        out = constrain(out, rule, ("batch", "seq", "act_embed"))
    return out


def route_topk(p, xt, m: MoEConfig):
    """DeepSeek-V3's ``noaux_tc`` router over all ``n_experts``: (experts
    (t, k) int32, combine weights (t, k) float32).

    ``s = sigmoid(x W)``, ``s' = s + bias``; a group's score is the sum of
    its two best ``s'`` and only the ``topk_group`` best groups' experts
    compete; the ``top_k`` best ``s'`` are chosen, weighted by their ``s``
    normalised to sum 1, times ``routed_scale``. The router's product is
    computed in full float32, as its weights are stored: a TPU's default
    would round both operands to bfloat16 and flip near-tied choices."""
    if m.router != "noaux_tc":
        raise ValueError(f"held experts route with 'noaux_tc', "
                         f"not {m.router!r}")
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"],
                        precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    sel = s + p["router_bias"]
    t, e = sel.shape
    groups = sel.reshape(t, m.n_group, e // m.n_group)
    g_score = jax.lax.top_k(groups, 2)[0].sum(-1)               # (t, G)
    _, g_idx = jax.lax.top_k(g_score, m.topk_group)
    keep = jax.nn.one_hot(g_idx, m.n_group, dtype=jnp.int32).sum(1)
    keep = jnp.repeat(keep > 0, e // m.n_group, axis=1)        # (t, e)
    _, idx = jax.lax.top_k(jnp.where(keep, sel, -jnp.inf), m.top_k)
    w = jnp.take_along_axis(s, idx, axis=1)
    return idx, w / (w.sum(-1, keepdims=True) + 1e-20) * m.routed_scale


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
    b, s, d = x.shape
    xt = rms_norm(x, p["ln"]).reshape(b * s, d)
    t = b * s
    c = HELD_CHUNK if t > HELD_CHUNK and t % HELD_CHUNK == 0 else t
    if c == t:
        out, sizes = _held_part(p, xt, m, layer)
    else:
        out, sizes = jax.lax.map(lambda xc: _held_part(p, xc, m, layer),
                                 xt.reshape(t // c, c, d))
        out, sizes = out.reshape(t, d), sizes.sum(0)
    out = out.reshape(b, s, d)
    if m.n_shared:
        out = out + mlp_apply(p["shared"], x, cfg)
    stats = jnp.stack([sizes.sum(), (sizes > 0).sum()]).astype(jnp.int32)
    return out, stats


def load_balance_stats(p, x, cfg):
    """Router entropy/load diagnostics (for logging; not an aux loss when
    router='sigmoid_bias' — DeepSeek-V3 trains aux-free)."""
    m = cfg.moe
    xt = rms_norm(x, p["ln"]).reshape(-1, cfg.d_model)
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, -1)
    load = probs.mean(0)
    return {"router_entropy": -(load * jnp.log(load + 1e-9)).sum(),
            "max_load": load.max() * m.n_experts}
