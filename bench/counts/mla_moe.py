"""Work one decode rank of an MLA + routed-expert LM (DeepSeek-V3 family)
needs, from its configuration's shapes and the program's routing counters.

The configuration is a dict with the Hugging Face key names; ``ep_size``
says over how many ranks each MoE layer's routed experts are divided, and
this rank holds ``n_routed_experts / ep_size`` of them.

- Flops: 2 per parameter of each matrix a token passes through, except
  the routed experts, which count 6 d f per (token, expert) pair that the
  held experts computed (the ``moe_pairs`` counter); attention as each
  form needs it: a prefill query scores each key before it with the
  expanded heads, 2 h (nope + rope + v) a pair; a decode query scores the
  latent rows in context directly (weight absorption), 2 h (2 kv_lora +
  rope) a row, never over the cache's ``max_len``. The head counts at the
  last prompt position and each decode step.
- Decode bytes: every weight but the routed experts once (the router in
  float32), each held expert's three matrices once for every layer in
  which it got a pair (the ``moe_active`` counter), the latent and rope
  rows in context, the new rows written, and the pairs' rows into and out
  of the expert matmuls.
"""
from __future__ import annotations


def dims(cfg: dict) -> dict:
    e = cfg["n_routed_experts"]
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "q": cfg["q_lora_rank"], "r": cfg["kv_lora_rank"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"], "ff": cfg["intermediate_size"],
            "fe": cfg["moe_intermediate_size"], "E": e,
            "held": e // max(cfg.get("ep_size", 1), 1),
            "shared": cfg["n_shared_experts"],
            "L": cfg["num_hidden_layers"],
            "Ld": cfg["first_k_dense_replace"], "V": cfg["vocab_size"]}


def mla_params(cfg: dict) -> int:
    """Matrix parameters of one MLA block: q down and up, kv down, the
    latent's key and value up-projections, output."""
    k = dims(cfg)
    return (k["d"] * k["q"] + k["q"] * k["h"] * (k["nope"] + k["rope"])
            + k["d"] * (k["r"] + k["rope"])
            + k["r"] * k["h"] * (k["nope"] + k["v"])
            + k["h"] * k["v"] * k["d"])


def expert_params(cfg: dict) -> int:
    """One routed expert's gate, up and down matrices."""
    k = dims(cfg)
    return 3 * k["d"] * k["fe"]


def token_params(cfg: dict) -> int:
    """Matrix parameters every token passes through outside attention
    scores and the routed experts: all layers' MLA, the dense layers'
    FFN, the MoE layers' shared experts and router."""
    k = dims(cfg)
    moe_layers = k["L"] - k["Ld"]
    return (k["L"] * mla_params(cfg) + k["Ld"] * 3 * k["d"] * k["ff"]
            + moe_layers * (k["shared"] * expert_params(cfg)
                            + k["d"] * k["E"]))


def weight_bytes(cfg: dict, elem_bytes: int) -> int:
    """Every weight a decode step reads once, the routed experts aside:
    matrices, norms, the float32 router and bias, the final norm and the
    head over the real vocabulary."""
    k = dims(cfg)
    moe_layers = k["L"] - k["Ld"]
    norms = (k["L"] * (2 * k["d"] + k["q"] + k["r"])
             + (moe_layers if k["shared"] else 0) * k["d"] + k["d"])
    router = moe_layers * 4 * (k["d"] * k["E"] + k["E"])
    matrices = token_params(cfg) - moe_layers * k["d"] * k["E"]
    return (elem_bytes * (matrices + norms + k["V"] * k["d"]) + router)


def prefill_attention_flops(cfg: dict, positions: int) -> int:
    """Expanded-form causal attention of ``positions`` queries from 0,
    each over the keys at and before it, all layers."""
    k = dims(cfg)
    keys = positions * (positions + 1) // 2
    return 2 * k["L"] * k["h"] * (k["nope"] + k["rope"] + k["v"]) * keys


def decode_attention_flops(cfg: dict, rows: int) -> int:
    """One absorbed-form decode query over ``rows`` latent rows, all
    layers: scores against latent and rope key, then the latent sum."""
    k = dims(cfg)
    return 2 * k["L"] * k["h"] * (2 * k["r"] + k["rope"]) * rows


def pair_flops(cfg: dict) -> int:
    """One (token, expert) pair through the held expert's SwiGLU."""
    return 2 * expert_params(cfg)


def pair_bytes(cfg: dict, elem_bytes: int) -> int:
    """One pair's rows into and out of the three expert matmuls: gate and
    up read d and write f each, down reads f and writes d."""
    k = dims(cfg)
    return elem_bytes * 3 * (k["d"] + k["fe"])


def request_flops(cfg: dict, prompt_len: int, n_new: int) -> int:
    """One request's flops outside the routed experts: prefill of the
    prompt, then ``n_new - 1`` decode steps (the routed experts' part is
    :func:`pair_flops` times the ``moe_pairs`` counter)."""
    k = dims(cfg)
    tokens = prompt_len + n_new - 1
    decode = sum(decode_attention_flops(cfg, p + 1)
                 for p in range(prompt_len, tokens))
    return (2 * token_params(cfg) * tokens
            + prefill_attention_flops(cfg, prompt_len) + decode
            + 2 * k["V"] * k["d"] * n_new)


def decode_step(cfg: dict, batch: int, pos: int, elem_bytes: int) -> dict:
    """``{"flops", "bytes"}`` of one decode step that writes position
    ``pos`` for ``batch`` rows, the routed experts aside (see
    :func:`routed`)."""
    k = dims(cfg)
    row = k["L"] * (k["r"] + k["rope"]) * elem_bytes
    flops = batch * (2 * token_params(cfg)
                     + decode_attention_flops(cfg, pos + 1)
                     + 2 * k["V"] * k["d"])
    return {"flops": flops,
            "bytes": (weight_bytes(cfg, elem_bytes) + batch * pos * row
                      + batch * row)}


def routed(cfg: dict, pairs: int, active: int, elem_bytes: int) -> dict:
    """``{"flops", "bytes"}`` the held experts need for ``pairs`` computed
    pairs, with ``active`` (layer, expert) weight sets that got a pair:
    each such expert's matrices once, and the pairs' rows."""
    return {"flops": pairs * pair_flops(cfg),
            "bytes": (active * expert_params(cfg) * elem_bytes
                      + pairs * pair_bytes(cfg, elem_bytes))}
