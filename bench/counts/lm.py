"""Work a dense decoder-only LM (GQA attention, gated MLP, tied head)
needs, from its configuration's shapes alone.

- Flops: 2 per parameter of each matrix a token passes through, and the
  attention a query really needs: causal, over the positions before it
  (never over the cache's ``max_len``). The head counts only where
  logits are needed: the last prompt position and each decode step.
- Decode bytes: the weights once (the head over the real vocabulary),
  the K/V of the positions in context, and the new K/V written.

The configuration is a dict with the Hugging Face key names."""
from __future__ import annotations


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"d": d, "h": h, "kvh": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or d // h,
            "ff": cfg["intermediate_size"], "L": cfg["num_hidden_layers"],
            "V": cfg["vocab_size"]}


def layer_matrix_params(cfg: dict) -> int:
    """Matrix parameters of one layer: q, k, v, o and gate, up, down."""
    k = dims(cfg)
    attn = k["d"] * k["hd"] * (2 * k["h"] + 2 * k["kvh"])
    return attn + 3 * k["d"] * k["ff"]


def weight_bytes(cfg: dict, elem_bytes: int) -> int:
    """Every weight a decode step reads once: the layers' matrices and
    norms, the final norm and the head over the real vocabulary."""
    k = dims(cfg)
    per_layer = layer_matrix_params(cfg) + 2 * k["d"]
    return elem_bytes * (k["L"] * per_layer + k["d"] + k["V"] * k["d"])


def attention_flops(cfg: dict, positions: int, start: int = 0) -> int:
    """Causal attention of queries at positions ``start .. start +
    positions - 1``, each over the keys at and before it, all layers."""
    k = dims(cfg)
    keys = sum(p + 1 for p in range(start, start + positions))
    return 4 * k["L"] * k["h"] * k["hd"] * keys


def request_flops(cfg: dict, prompt_len: int, n_new: int) -> int:
    """One request of ``prompt_len`` prompt tokens and ``n_new`` greedy
    tokens: prefill of the prompt, then ``n_new - 1`` decode steps."""
    k = dims(cfg)
    tokens = prompt_len + n_new - 1
    return (2 * layer_matrix_params(cfg) * k["L"] * tokens
            + attention_flops(cfg, tokens)
            + 2 * k["V"] * k["d"] * n_new)


def decode_step(cfg: dict, batch: int, pos: int, elem_bytes: int) -> dict:
    """``{"flops", "bytes"}`` of one decode step that writes position
    ``pos`` for ``batch`` rows, each with ``pos`` positions before it."""
    k = dims(cfg)
    kv_row = 2 * k["L"] * k["kvh"] * k["hd"] * elem_bytes
    flops = batch * (2 * layer_matrix_params(cfg) * k["L"]
                     + attention_flops(cfg, 1, pos)
                     + 2 * k["V"] * k["d"])
    return {"flops": flops,
            "bytes": (weight_bytes(cfg, elem_bytes)
                      + batch * pos * kv_row + batch * kv_row)}
