"""The Qwen2 architecture's work module (a configuration's ``"work"``,
loaded through ``bench/arch.py``): the sizes the program's model must
have, and the operations and bytes the model's work needs, from the
configuration's own sizes (the JSON under ``bench/configs``), never from
the program.

``roofline_s``, ``matmul_call``, ``block_mask``, ``BF16`` and ``F32``
know no architecture; another architecture's work module imports them
from here.

Counts are of useful work: a sparse matrix counts its nonzero blocks, a
decode step its live slots and the cache positions they attend, a
prefill its true prompt tokens.  Padding, tile occupancy and relayouts
are not work.  Weights and cache are bf16 (2 bytes) as the
configurations state; norm scales are float32.
"""
from __future__ import annotations

import numpy as np

BF16 = 2
F32 = 4


def dims(c: dict) -> dict:
    d, h = c["hidden_size"], c["num_attention_heads"]
    return {"layers": c["num_hidden_layers"], "d": d, "heads": h,
            "kv_heads": c["num_key_value_heads"], "head_dim": d // h,
            "d_ff": c["intermediate_size"], "vocab": c["vocab_size"]}


def program_sizes(c: dict) -> dict:
    """The program ``ModelCfg`` attributes that must equal the file's
    published sizes."""
    g = dims(c)
    want = {"d_model": g["d"], "num_layers": g["layers"],
            "num_heads": g["heads"], "num_kv_heads": g["kv_heads"],
            "head_dim": g["head_dim"], "d_ff": g["d_ff"],
            "vocab_size": g["vocab"], "rope_theta": c["rope_theta"],
            "norm_eps": c["rms_norm_eps"],
            "tie_embeddings": c["tie_word_embeddings"],
            "dtype": c["torch_dtype"], "qkv_bias": c["qkv_bias"],
            "act": c["hidden_act"]}
    if c.get("sparse_ffn"):
        want["ffn_block_size"] = c["sparse_ffn"]["block_size"]
    return want


def block_mask(m: int, k: int, b: int, density: float, seed: int):
    """Uniform random block mask ``[m/b, k/b]`` with exactly
    ``round(density * blocks)`` blocks, drawn as the configuration's
    ``sparse_ffn.mask`` states (numpy ``default_rng(seed).choice``)."""
    mb, kb = m // b, k // b
    total = mb * kb
    nnz = min(total, max(1, int(round(density * total))))
    mask = np.zeros((mb, kb), bool)
    mask.flat[np.random.default_rng(seed).choice(total, size=nnz,
                                                 replace=False)] = True
    return mask


def ffn_masks(c: dict) -> dict:
    """The sparse FFN's block masks by matrix name (``None`` when the
    FFN is dense).  ``[out/b, in/b]``, the same in every layer."""
    s = c.get("sparse_ffn")
    if not s:
        return None
    g = dims(c)
    shapes = {"up": (g["d_ff"], g["d"]), "gate": (g["d_ff"], g["d"]),
              "down": (g["d"], g["d_ff"])}
    return {name: block_mask(m, k, s["block_size"], s["density"],
                             s["mask_seeds"][name])
            for name, (m, k) in shapes.items()}


def matmuls(c: dict) -> list:
    """The weight matmuls of one layer: ``(name, m_out, k_in, nnz_blocks,
    block)``; ``nnz_blocks`` is ``None`` for a dense matrix."""
    g = dims(c)
    d, qd, kvd = g["d"], g["heads"] * g["head_dim"], \
        g["kv_heads"] * g["head_dim"]
    out = [("wq", qd, d, None, None), ("wk", kvd, d, None, None),
           ("wv", kvd, d, None, None), ("wo", d, qd, None, None)]
    masks = ffn_masks(c)
    for name, m, k in (("up", g["d_ff"], d), ("gate", g["d_ff"], d),
                       ("down", d, g["d_ff"])):
        if masks is None:
            out.append((name, m, k, None, None))
        else:
            b = c["sparse_ffn"]["block_size"]
            out.append((name, m, k, int(masks[name].sum()), b))
    return out


def matmul_params(m: int, k: int, nnz, b) -> int:
    return m * k if nnz is None else nnz * b * b


def param_count(c: dict) -> int:
    """Every parameter: embedding (tied with the output head), per layer
    the projections, the q/k/v biases where ``qkv_bias`` says so, the
    FFN at its nonzero blocks, two norm scales per layer and the final
    norm."""
    g = dims(c)
    per_layer = sum(matmul_params(m, k, nnz, b)
                    for _, m, k, nnz, b in matmuls(c))
    if c["qkv_bias"]:
        per_layer += (g["heads"] + 2 * g["kv_heads"]) * g["head_dim"]
    per_layer += 2 * g["d"]
    total = g["layers"] * per_layer + g["vocab"] * g["d"] + g["d"]
    if not c.get("tie_word_embeddings", True):
        total += g["vocab"] * g["d"]
    return total


def weight_bytes(c: dict) -> int:
    """Stored size of the weights: bf16 matrices, biases and embedding,
    float32 norm scales."""
    g = dims(c)
    norms = (2 * g["layers"] + 1) * g["d"]
    return (param_count(c) - norms) * BF16 + norms * F32


def kv_bytes_per_position(c: dict) -> int:
    g = dims(c)
    return g["layers"] * 2 * g["kv_heads"] * g["head_dim"] * BF16


def kv_cache_bytes(c: dict, batch: int, max_len: int) -> int:
    return batch * max_len * kv_bytes_per_position(c)


def layer_matmul_flops_per_token(c: dict) -> int:
    return 2 * sum(matmul_params(m, k, nnz, b)
                   for _, m, k, nnz, b in matmuls(c))


def attention_flops(c: dict, queries_ctx) -> float:
    """QK and PV products of every layer: ``4 * heads * head_dim`` per
    (query, visible key) pair; ``queries_ctx`` lists the visible keys of
    each query."""
    g = dims(c)
    return 4.0 * g["layers"] * g["heads"] * g["head_dim"] * float(
        np.sum(queries_ctx))


def decode_step(c: dict, contexts) -> tuple:
    """``(flops, bytes)`` of one decode step over the live slots, each
    attending ``contexts[i]`` cache positions (its new token included)."""
    g = dims(c)
    live = len(contexts)
    flops = live * (g["layers"] * layer_matmul_flops_per_token(c)
                    + 2 * g["vocab"] * g["d"])
    flops += attention_flops(c, contexts)
    nbytes = weight_bytes(c) + kv_bytes_per_position(c) * float(
        np.sum(contexts))
    return float(flops), float(nbytes)


def prefill(c: dict, n: int) -> tuple:
    """``(flops, bytes)`` of prefilling ``n`` true prompt tokens: every
    layer over ``n`` tokens, causal attention, the output head at the
    last token; the weights read once and the cache written once."""
    g = dims(c)
    flops = n * g["layers"] * layer_matmul_flops_per_token(c)
    flops += attention_flops(c, np.arange(1, n + 1))
    flops += 2 * g["vocab"] * g["d"]
    nbytes = weight_bytes(c) + kv_bytes_per_position(c) * n
    return float(flops), float(nbytes)


def matmul_call(m: int, k: int, n: int, nnz=None, b=None) -> tuple:
    """``(flops, bytes)`` of one ``[m, k] x [k, n]`` weight product in
    bf16: the nonzero blocks' products, the stored weight, the input and
    the output read or written once."""
    p = matmul_params(m, k, nnz, b)
    return float(2 * p * n), float((p + k * n + m * n) * BF16)


def roofline_s(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the bandwidth bound."""
    return max(flops / peaks["flops_per_s"], nbytes / peaks["bytes_per_s"])


def kernel_bound_s(c: dict, plans: list, n: int, routes, peaks: dict
                   ) -> float:
    """Roofline seconds of one program's weight products at ``n`` tokens
    that the program's plans (``sparse.plan_report`` rows: ``route``,
    ``kind``, ``shape``) give one of ``routes``, over all layers.  Where
    several matrices of a layer share a shape, the plans of that shape
    are shared out among them."""
    groups = {}
    for _, m, k, nnz, b in matmuls(c):
        groups.setdefault((m, k, nnz is not None), []).append((nnz, b))
    total = 0.0
    for (m, k, sparse), mats in groups.items():
        kind = "static" if sparse else "dense"
        ps = [p for p in plans
              if tuple(p["shape"]) == (m, k, n) and p["kind"] == kind]
        if not ps:
            continue
        share = sum(p["route"] in routes for p in ps) / len(ps)
        for nnz, b in mats:
            total += share * roofline_s(*matmul_call(m, k, n, nnz, b), peaks)
    return total * dims(c)["layers"]
