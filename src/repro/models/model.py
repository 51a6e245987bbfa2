"""Top-level language model: embeddings + stack(s) + loss / prefill / decode.

One class covers all 10 assigned architectures; family differences are
entirely config-driven (``configs/*.py``):

* dense / MoE / hybrid / SSM decoder-only LMs,
* VLM (``frontend="vision"``): precomputed patch embeddings are prepended
  to the token sequence (frontend itself is a stub per the brief),
* audio enc-dec (``encoder_layers > 0``): precomputed frame embeddings run
  through a bidirectional encoder; decoder layers cross-attend.

Entry points map 1:1 onto the assigned shape cells:

* ``loss``         -> train_4k (train_step)
* ``prefill``      -> prefill_32k (returns last-token logits + caches)
* ``decode_step``  -> decode_32k / long_500k (one token against a cache;
  ``retained=True`` switches to the ring-buffer local+global cache that
  makes 500k-context decode O(window) -- the paper's static block
  sparsity applied to the KV cache, DESIGN.md §3)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer as tfm
from repro.models.config import LayerSpec, ModelCfg
from repro.models.layers import embed, embed_init, rms_norm, unembed
from repro.sharding.rules import constrain


def _dtype(cfg: ModelCfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def pack_sparse(cfg: ModelCfg, params):
    """Forward-only serving with fixed weights: ``(params', packed)``
    where ``params'`` holds, beside each block-sparse FFN matrix's
    values, its stack of kernel tiles (``SparseLinear.pack``) under
    ``packed``, so ``LM.prefill``/``decode_step`` skip the per-call
    relayout; ``params`` is left as it is.  One jitted packing per
    matrix, over its layer stack.  ``packed`` counts the matrices (one
    per layer) and the tiles' device bytes."""
    served = jax.tree.map(lambda a: a, params)          # fresh containers
    matrices = nbytes = 0
    for gi, si, name, layer in tfm.sparse_linears(cfg):
        p = served["stack"][gi][si]["ffn"][name]
        p["packed"] = jax.jit(layer.pack)(p["values"])
        matrices += int(np.prod(p["values"].shape[:-3]))
        nbytes += int(p["packed"].tiles.nbytes)
    return served, {"matrices": matrices, "bytes": nbytes}


@dataclasses.dataclass(frozen=True)
class LM:
    cfg: ModelCfg

    # -- encoder structure (enc-dec archs) ---------------------------------
    @property
    def encoder_groups(self):
        if not self.cfg.encoder_layers:
            return ()
        spec = LayerSpec(mixer="attn", ffn="mlp", causal=False)
        return (((spec,), self.cfg.encoder_layers),)

    def _encoder_cfg(self) -> ModelCfg:
        return dataclasses.replace(self.cfg, groups=self.encoder_groups)

    # -- init ---------------------------------------------------------------
    def init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        dt = _dtype(cfg)
        ks = jax.random.split(key, 4)
        params: Dict[str, Any] = {
            "embed": embed_init(ks[0], cfg.vocab_size, cfg.d_model, dtype=dt),
            "stack": tfm.stack_init(ks[1], cfg, dtype=dt),
            "final_norm": {"scale": jnp.ones((cfg.d_model,), jnp.float32)},
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = embed_init(ks[2], cfg.vocab_size,
                                           cfg.d_model, dtype=dt)
        if cfg.encoder_layers:
            ecfg = self._encoder_cfg()
            params["encoder"] = tfm.stack_init(ks[3], ecfg, dtype=dt)
            params["enc_norm"] = {"scale": jnp.ones((cfg.d_model,),
                                                    jnp.float32)}
        return params

    # -- shared plumbing -----------------------------------------------------
    def _embed_tokens(self, params, tokens):
        cfg = self.cfg
        with jax.named_scope("embed"):
            h = embed(params["embed"], tokens)
            if cfg.embed_scale:
                h = h * jnp.asarray(np.sqrt(cfg.d_model), h.dtype)
        return h

    def _unembed(self, params, h):
        cfg = self.cfg
        table = params["lm_head" if "lm_head" in params else "embed"]
        with jax.named_scope("unembed"):
            return unembed(table, h, softcap=cfg.final_softcap)

    def _encode(self, params, enc_frames):
        """Bidirectional encoder over precomputed frame embeddings."""
        ecfg = self._encoder_cfg()
        t = enc_frames.shape[1]
        positions = jnp.arange(t)[None, :]
        h, _ = tfm.stack_apply(params["encoder"], ecfg, enc_frames,
                               positions=positions)
        return rms_norm(params["enc_norm"], h, eps=ecfg.norm_eps,
                        plus_one=ecfg.post_norm)

    def _prepare(self, params, tokens, frontend, enc_frames):
        """Returns (h, positions, memory, n_prefix)."""
        h = self._embed_tokens(params, tokens)
        n_prefix = 0
        if frontend is not None:
            h = jnp.concatenate([frontend.astype(h.dtype), h], axis=1)
            n_prefix = frontend.shape[1]
        h = constrain(h, "batch", None, None)
        positions = jnp.arange(h.shape[1])[None, :]
        memory = None
        if enc_frames is not None:
            memory = constrain(self._encode(params, enc_frames),
                               "batch", None, None)
        return h, positions, memory, n_prefix

    # -- training forward + loss ---------------------------------------------
    def forward(self, params, tokens, *, frontend=None, enc_frames=None,
                schedule=None):
        """Full-sequence logits [B, S(+F), V] and stack metrics."""
        cfg = self.cfg
        h, positions, memory, n_prefix = self._prepare(
            params, tokens, frontend, enc_frames)
        h, metrics = tfm.stack_apply(params["stack"], cfg, h,
                                     positions=positions, memory=memory,
                                     schedule=schedule)
        h = rms_norm(params["final_norm"], h, eps=cfg.norm_eps,
                     plus_one=cfg.post_norm)
        if n_prefix:
            h = h[:, n_prefix:]
        return self._unembed(params, h), metrics

    def loss(self, params, batch, *, loss_chunk: int = 1024,
             schedule=None):
        """Next-token cross entropy.  batch: {"tokens": [B,S] int32,
        "targets": [B,S] int32 (-1 = pad), "frontend"?, "enc_frames"?}.

        The unembed projection + softmax run chunked over the sequence so
        the [B, S, V] logits tensor is never materialized (the V-dim is
        vocab-sharded under pjit; the chunk loop bounds the fp32 buffer).
        """
        cfg = self.cfg
        h, positions, memory, n_prefix = self._prepare(
            params, batch["tokens"], batch.get("frontend"),
            batch.get("enc_frames"))
        h, metrics = tfm.stack_apply(params["stack"], cfg, h,
                                     positions=positions, memory=memory,
                                     schedule=schedule)
        h = rms_norm(params["final_norm"], h, eps=cfg.norm_eps,
                     plus_one=cfg.post_norm)
        if n_prefix:
            h = h[:, n_prefix:]
        targets = batch["targets"]
        b_, s = targets.shape
        c = min(loss_chunk, s)
        while s % c:
            c //= 2
        hc = constrain(h.reshape(b_, s // c, c, -1).transpose(1, 0, 2, 3),
                       None, "batch", None, None)
        tc = targets.reshape(b_, s // c, c).transpose(1, 0, 2)

        def chunk_loss(carry, inp):
            hx, tx = inp
            hx = constrain(hx, "batch", None, None)
            logits = self._unembed(params, hx).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(
                logits, jnp.maximum(tx, 0)[..., None], axis=-1)[..., 0]
            valid = (tx >= 0).astype(jnp.float32)
            nll = (lse - gold) * valid
            tot, cnt = carry
            return (tot + nll.sum(), cnt + valid.sum()), None

        (tot, cnt), _ = jax.lax.scan(chunk_loss,
                                     (jnp.zeros((), jnp.float32),
                                      jnp.zeros((), jnp.float32)),
                                     (hc, tc))
        xent = tot / jnp.maximum(cnt, 1.0)
        loss = xent
        if cfg.moe is not None:
            loss = loss + cfg.moe.router_aux_weight * metrics["aux_loss"] \
                + cfg.moe.router_z_weight * metrics["z_loss"]
        metrics = dict(metrics, xent=xent)
        return loss, metrics

    # -- serving ---------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, *,
                   memory_len: int = 0):
        return tfm.stack_cache_init(self.cfg, batch, max_len,
                                    dtype=_dtype(self.cfg),
                                    memory_len=memory_len)

    def prefill(self, params, tokens, *, max_len: int, frontend=None,
                enc_frames=None, schedule=None, last_index=None):
        """Returns (last-token logits [B, V], populated caches).

        ``last_index`` (``[B]`` int32, optional) gathers the logits at a
        per-row position instead of the literal last one -- the serving
        engine's bucketed prefill pads prompts up to a shape bucket, so
        the *true* last prompt token sits at ``len(prompt) - 1``, not at
        ``bucket - 1``.  Indices are into the (frontend-concatenated)
        sequence; a traced value is fine (dynamic gather, no recompile
        per prompt length).  Causality makes the pad suffix inert here:
        positions ``<= last_index`` never attend to it, and decode masks
        cache slots ``> position``, so padded rows are never read before
        they are overwritten."""
        cfg = self.cfg
        h, positions, memory, n_prefix = self._prepare(
            params, tokens, frontend, enc_frames)
        h, caches = tfm.stack_prefill(params["stack"], cfg, h,
                                      positions=positions, max_len=max_len,
                                      memory=memory, schedule=schedule)
        if last_index is None:
            h = h[:, -1:]
        else:
            idx = jnp.asarray(last_index, jnp.int32).reshape(-1, 1, 1)
            h = jnp.take_along_axis(
                h, jnp.broadcast_to(idx, (h.shape[0], 1, h.shape[2])),
                axis=1)
        h = rms_norm(params["final_norm"], h, eps=cfg.norm_eps,
                     plus_one=cfg.post_norm)
        return self._unembed(params, h)[:, 0], caches

    def _ring_slot(self, positions):
        """Physical cache slot for retained-block (local+global) caches."""
        cfg = self.cfg
        g, w = cfg.retained_prefix, cfg.retained_window
        return jnp.where(positions < g + w, positions,
                         g + (positions - g) % w)

    def decode_step(self, params, tokens, caches, positions, *,
                    retained: bool = False):
        """One token: tokens [B, 1], positions [B].  Returns
        (logits [B, V], new caches)."""
        cfg = self.cfg
        h = self._embed_tokens(params, tokens)
        slot = self._ring_slot(positions) if retained else positions
        h, caches = tfm.stack_decode(params["stack"], cfg, h, caches,
                                     positions=positions, slot=slot,
                                     window_filter=not retained)
        h = rms_norm(params["final_norm"], h, eps=cfg.norm_eps,
                     plus_one=cfg.post_norm)
        return self._unembed(params, h)[:, 0], caches
