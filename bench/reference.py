"""Plain float32 reference of the Qwen2 architecture, and the benchmark's
own weights.

The forward pass follows the published description (Qwen2 technical
report, arXiv:2407.10671; the Hugging Face ``Qwen2ForCausalLM``): token
embedding; per layer a pre-norm RMSNorm, grouped-query attention with
q/k/v biases (where the configuration's ``qkv_bias`` says so) and
rotary embeddings (``rope_theta``, halves rotated),
causal softmax, the output projection and the residual; a pre-norm
SwiGLU FFN (``down(silu(gate x) * up x)``) and the residual; a final
RMSNorm and the output head tied to the embedding.  It runs in float32
under ``default_matmul_precision("highest")``, one sequence at a time,
layer by layer over the bf16 weights, with no kernel, cache or batching.
For a configuration with a ``sparse_ffn`` the FFN matrices are the dense
matrices with every block outside the mask at zero.

The benchmark makes the weights (``make_weights``), in the layout the
served program takes, and hands the same bf16 values to the program and,
made again after the program has been freed, to this reference.  It
imports nothing of the program.

``control=True`` computes the same forward pass with every product's
operands rounded to float8 e4m3 (a scale per row of each operand along
the contracted axis, float32 accumulation): the precision one step below
the bf16 that the configurations state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import work

F8_MAX = 448.0          # largest finite float8_e4m3fn


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def layout(c: dict) -> dict:
    """ShapeDtypeStructs of the served parameters, in the program's
    layout: one scanned group of ``num_hidden_layers`` identical layers."""
    g = work.dims(c)
    L, d, ff = g["layers"], g["d"], g["d_ff"]
    qd, kvd = g["heads"] * g["head_dim"], g["kv_heads"] * g["head_dim"]
    bf, f32 = jnp.bfloat16, jnp.float32

    def s(shape, dt=bf):
        return jax.ShapeDtypeStruct(shape, dt)
    masks = work.ffn_masks(c)
    if masks is None:
        ffn = {"up": {"w": s((L, d, ff))}, "gate": {"w": s((L, d, ff))},
               "down": {"w": s((L, ff, d))}}
    else:
        b = c["sparse_ffn"]["block_size"]
        ffn = {name: {"values": s((L, int(m.sum()), b, b))}
               for name, m in masks.items()}
    attn = {"wq": {"w": s((L, d, qd))}, "wk": {"w": s((L, d, kvd))},
            "wv": {"w": s((L, d, kvd))}, "wo": {"w": s((L, qd, d))}}
    if c["qkv_bias"]:
        for name, n in (("wq", qd), ("wk", kvd), ("wv", kvd)):
            attn[name]["b"] = s((L, n))
    layer = {
        "norm1": {"scale": s((L, d), f32)},
        "attn": attn,
        "norm2": {"scale": s((L, d), f32)},
        "ffn": ffn,
    }
    return {"embed": {"table": s((g["vocab"], d))},
            "final_norm": {"scale": s((d,), f32)},
            "stack": [[layer]]}


def _scale(path: str, shape, c: dict) -> float:
    if path.endswith("table"):
        return 0.02                               # initializer_range
    if path.endswith("/b"):
        return 0.1
    if path.endswith("scale"):
        return 0.1                                # around 1, see below
    if path.endswith("values"):                   # fan-in at density
        k = c["intermediate_size"] if "/down/" in path else c["hidden_size"]
        return 1.0 / np.sqrt(k * c["sparse_ffn"]["density"])
    return 1.0 / np.sqrt(shape[-2])               # [.., d_in, d_out]


def seed_key(seed: int):
    """A PRNG key from a seed of up to 64 bits."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def make_weights(c: dict, seed: int):
    """Every weight from the seed, on the device, in one jitted call:
    normal draws at fan-in scale, in the dtype they are served in; norm
    scales are ``1 + 0.1 N(0, 1)`` and biases ``0.1 N(0, 1)``, so that
    neither is the identity."""
    shapes = layout(c)
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)

    def make(key):
        leaves = []
        for i, (path, sd) in enumerate(flat):
            name = jax.tree_util.keystr(path, simple=True, separator="/")
            x = jax.random.normal(jax.random.fold_in(key, i), sd.shape,
                                  sd.dtype) * jnp.asarray(
                                      _scale(name, sd.shape, c), sd.dtype)
            if name.endswith("scale"):
                x = x + 1
            leaves.append(x.astype(sd.dtype))
        return jax.tree_util.tree_unflatten(tree, leaves)
    return jax.jit(make)(seed_key(seed))


# ---------------------------------------------------------------------------
# forward pass
# ---------------------------------------------------------------------------

def _q8(t, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(t), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (t / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _einsum(spec: str, a, b, control: bool):
    if control:
        ins, _ = spec.split("->")
        sa, sb = ins.split(",")
        contracted = set(sa) & set(sb) - set(spec.split("->")[1])
        a = _q8(a, tuple(i for i, ch in enumerate(sa) if ch in contracted))
        b = _q8(b, tuple(i for i, ch in enumerate(sb) if ch in contracted))
    return jnp.einsum(spec, a, b)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _rope(x, pos, theta):
    """Rotary embedding, halves rotated: x ``[P, H, dh]``."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos[:, None].astype(jnp.float32) * inv          # [P, dh/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _dense(values, mask: np.ndarray):
    """``[k_in, m_out]`` matrix of a block-sparse ``[m_out, k_in]``
    weight: the blocks in row-major order of the mask, zero elsewhere."""
    mb, kb = mask.shape
    b = values.shape[-1]
    rows, cols = np.nonzero(mask)
    full = jnp.zeros((mb, kb, b, b), jnp.float32).at[rows, cols].set(
        values.astype(jnp.float32))
    return full.transpose(0, 2, 1, 3).reshape(mb * b, kb * b).T


def hidden(w, c: dict, tokens, control: bool = False):
    """Final-norm hidden states ``[P, d]`` of one causal sequence."""
    g = work.dims(c)
    h, kv, dh = g["heads"], g["kv_heads"], g["head_dim"]
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    masks = work.ffn_masks(c)
    P = tokens.shape[0]
    pos = jnp.arange(P)
    causal = pos[:, None] >= pos[None, :]
    f32 = jnp.float32

    def mm(x, wt):
        return _einsum("pk,km->pm", x, wt.astype(f32), control)

    def ffn_w(lw, name):
        if masks is None:
            return lw["ffn"][name]["w"]
        return _dense(lw["ffn"][name]["values"], masks[name])

    def layer(x, lw):
        a = lw["attn"]
        xn = _rms(x, lw["norm1"]["scale"], eps)
        def proj(name, heads):
            y = mm(xn, a[name]["w"])
            if "b" in a[name]:
                y = y + a[name]["b"]
            return y.reshape(P, heads, dh)
        q, k, v = proj("wq", h), proj("wk", kv), proj("wv", kv)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        k = jnp.repeat(k, h // kv, axis=1)
        v = jnp.repeat(v, h // kv, axis=1)
        s = _einsum("qhd,khd->hqk", q, k, control) / np.sqrt(dh)
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
        o = _einsum("hqk,khd->qhd", p, v, control).reshape(P, h * dh)
        x = x + mm(o, a["wo"]["w"])
        xn = _rms(x, lw["norm2"]["scale"], eps)
        u = mm(xn, ffn_w(lw, "up"))
        gt = mm(xn, ffn_w(lw, "gate"))
        return x + mm(jax.nn.silu(gt) * u, ffn_w(lw, "down")), None

    x = w["embed"]["table"][tokens].astype(f32)
    x, _ = jax.lax.scan(layer, x, w["stack"][0][0])
    return _rms(x, w["final_norm"]["scale"], eps)


ROWS = 256              # output-head rows per call


@functools.partial(jax.jit, static_argnames=("control",))
def _head_rows(table, h_ref, served, h_ctl, *, control: bool):
    """Per row: the reference's best logit minus the served token's, and
    (``control``) minus the logit of the token the control puts first."""
    t = table.astype(jnp.float32)
    ref = jnp.einsum("rd,vd->rv", h_ref, t)
    best = ref.max(-1)
    gap = best - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
    if not control:
        return gap, jnp.zeros_like(gap)
    ctl = _einsum("rd,vd->rv", h_ctl, t, True).argmax(-1)
    return gap, best - jnp.take_along_axis(ref, ctl[:, None], -1)[:, 0]


class Reference:
    """The reference over one configuration's weights; sequences are
    zero-padded to ``length`` so that one program serves them all."""

    def __init__(self, c: dict, weights, length: int):
        self.c, self.w, self.length = c, weights, length
        self._hidden = jax.jit(lambda w, t: hidden(w, c, t))
        self._hidden_ctl = jax.jit(lambda w, t: hidden(w, c, t, True))

    def gaps(self, prompt, output, control: bool = False):
        """Logit gaps of every served token of one request: ``(served,
        control)`` arrays; the control's is empty without ``control``."""
        prompt = np.asarray(prompt, np.int32)
        output = np.asarray(output, np.int32)
        seq = np.concatenate([prompt, output[:-1]])
        toks = np.zeros((self.length,), np.int32)
        toks[:len(seq)] = seq
        rows = np.arange(len(prompt) - 1, len(seq))
        with jax.default_matmul_precision("highest"):
            h = self._hidden(self.w, toks)
            hc = self._hidden_ctl(self.w, toks) if control else h
            out_s, out_c = [], []
            for i in range(0, len(rows), ROWS):
                r = rows[i:i + ROWS]
                pad = np.zeros((ROWS,), np.int64)
                pad[:len(r)] = r
                srv = np.zeros((ROWS,), np.int32)
                srv[:len(r)] = output[i:i + ROWS]
                gs, gc = _head_rows(self.w["embed"]["table"], h[pad],
                                    jnp.asarray(srv), hc[pad],
                                    control=control)
                out_s.append(np.asarray(gs)[:len(r)])
                out_c.append(np.asarray(gc)[:len(r)])
        return (np.concatenate(out_s),
                np.concatenate(out_c) if control else np.zeros(0))
