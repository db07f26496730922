"""Model facade: init / forward / loss / prefill / decode for every family.

``build_model(cfg)`` returns a ``Model`` whose methods are pure functions of
(params, batch) — suitable for jit/pjit, the SSR pipeline executor, and the
dry-run's ``.lower().compile()``.

Input contracts per family (see ``input_specs``):
  * LM (dense/moe/hybrid/ssm):  tokens (B,S) i32, labels (B,S) i32
  * vlm:   embeds (B,S,D) — merged text+vision embeddings (vision tower is a
           stub per the assignment), positions (3,B,S) for M-RoPE
  * audio: enc_embeds (B,S,D) frame embeddings (conv frontend stub),
           dec_tokens (B,S/4), labels (B,S/4)
  * vision (DeiT): embeds (B,197,D) patch embeddings, labels (B,) classes
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import layers as L
from repro.models import transformer as T

AUDIO_DECODER_RATIO = 4  # decoder length = seq_len // 4 for audio shapes


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


@partial(jax.jit, static_argnums=0)
def _init_params(cfg: ModelConfig, rng):
    return Model(cfg)._init(rng)


@dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    # ------------------------------------------------------------------ init
    def init(self, rng) -> Dict[str, Any]:
        """Random parameters from ``rng``, built by ONE jitted program on
        the default device.  Run eagerly, each stacked leaf would exist
        as float32 temporaries (draw, then scale) before its cast to
        ``param_dtype`` — at published widths several times the bf16
        weights; fused, peak memory stays near the weights themselves."""
        return _init_params(self.cfg, rng)

    def _init(self, rng) -> Dict[str, Any]:
        cfg = self.cfg
        ks = L.split_keys(rng, 6)
        params: Dict[str, Any] = {}
        if cfg.family == "audio":
            params["enc_stack"] = T.init_stack(ks[0], cfg)
            params["enc_norm"] = L.init_norm(cfg)
            params["embed"] = L.init_embedding(ks[1], cfg)
            params["stack"] = T.init_stack(ks[2], cfg, cross=True)
            params["final_norm"] = L.init_norm(cfg)
        elif cfg.family == "vision":
            params["pos_embed"] = 0.02 * jax.random.normal(
                ks[0], (1, 256, cfg.d_model), jnp.float32)
            params["cls"] = jnp.zeros((1, 1, cfg.d_model), jnp.float32)
            params["stack"] = T.init_stack(ks[2], cfg)
            params["final_norm"] = L.init_norm(cfg)
            params["head"] = {"w": L.dense_init(
                ks[3], (cfg.d_model, cfg.vocab_size), cfg.d_model,
                jnp.dtype(cfg.param_dtype))}
        else:  # LM families (dense / moe / hybrid / ssm / vlm)
            params["embed"] = L.init_embedding(ks[1], cfg)
            params["stack"] = T.init_stack(ks[2], cfg)
            params["final_norm"] = L.init_norm(cfg)
            if not cfg.tie_embeddings:
                params["head"] = {"w": L.dense_init(
                    ks[3], (cfg.d_model, cfg.vocab_size), cfg.d_model,
                    jnp.dtype(cfg.param_dtype))}
        return params

    # --------------------------------------------------------------- forward
    def _lm_hidden(self, params, x, *, positions=None, cache=None,
                   cache_index=None, remat=False, collect_state=False,
                   block_tables=None, write_tables=None):
        cfg = self.cfg
        x = T.shard_act(x)
        x, new_cache, aux = T.run_stack(
            params["stack"], x, cfg, positions=positions, causal=True,
            cache=cache, cache_index=cache_index, remat=remat,
            collect_state=collect_state, block_tables=block_tables,
            write_tables=write_tables)
        x = L.apply_norm(params["final_norm"], x, cfg)
        return x, new_cache, aux

    def _lm_trunk(self, params, x, *, positions=None, cache=None,
                  cache_index=None, remat=False, collect_state=False,
                  block_tables=None):
        x, new_cache, aux = self._lm_hidden(
            params, x, positions=positions, cache=cache,
            cache_index=cache_index, remat=remat,
            collect_state=collect_state, block_tables=block_tables)
        logits = L.logits_head(params.get("embed"), params.get("head"), x,
                               self.cfg)
        return logits, new_cache, aux

    def _head_weight(self, params):
        cfg = self.cfg
        if cfg.tie_embeddings or params.get("head") is None:
            return params["embed"]["table"].T
        return params["head"]["w"]

    def _use_chunked_ce(self) -> bool:
        import os
        thresh = int(os.environ.get("REPRO_CHUNKED_CE", 65536))
        return bool(thresh) and self.cfg.vocab_size >= thresh \
            and self.cfg.family not in ("vision",)

    def forward(self, params, batch, *, remat: bool = False):
        """Full forward pass -> (logits, aux_loss)."""
        cfg = self.cfg
        if cfg.family == "vision":
            x = batch["embeds"].astype(cfg.dtype)
            b, s, _ = x.shape
            cls = jnp.broadcast_to(params["cls"].astype(cfg.dtype),
                                   (b, 1, cfg.d_model))
            x = jnp.concatenate([cls, x], axis=1)
            x = x + params["pos_embed"][:, :s + 1].astype(cfg.dtype)
            x, _, aux = T.run_stack(params["stack"], x, cfg, causal=False,
                                    remat=remat)
            x = L.apply_norm(params["final_norm"], x, cfg)
            logits = jnp.einsum("bd,dv->bv", x[:, 0], params["head"]["w"],
                                preferred_element_type=jnp.float32)
            return logits, aux
        if cfg.family == "audio":
            enc = self.encode(params, batch["enc_embeds"], remat=remat)
            y = L.embed(params["embed"], batch["dec_tokens"], cfg)
            y = y.astype(cfg.dtype)
            pos = L.sinusoidal_positions(y.shape[1], cfg.d_model)
            y = y + pos[None].astype(cfg.dtype)
            y, _, aux = T.run_stack(params["stack"], y, cfg, causal=True,
                                    enc_out=enc, remat=remat)
            y = L.apply_norm(params["final_norm"], y, cfg)
            logits = L.logits_head(params["embed"], params.get("head"), y, cfg)
            return logits, aux
        # LM
        if "embeds" in batch:
            x = batch["embeds"].astype(cfg.dtype)
        else:
            x = L.embed(params["embed"], batch["tokens"], cfg).astype(cfg.dtype)
        logits, _, aux = self._lm_trunk(
            params, x, positions=batch.get("positions"), remat=remat)
        return logits, aux

    def encode(self, params, enc_embeds, *, remat=False):
        cfg = self.cfg
        x = enc_embeds.astype(cfg.dtype)
        pos = L.sinusoidal_positions(x.shape[1], cfg.d_model)
        x = x + pos[None].astype(cfg.dtype)
        x, _, _ = T.run_stack(params["enc_stack"], x, cfg, causal=False,
                              remat=remat)
        return L.apply_norm(params["enc_norm"], x, cfg)

    # ------------------------------------------------------------------ loss
    def loss(self, params, batch, *, remat: bool = False):
        cfg = self.cfg
        labels = batch["labels"]
        if cfg.family == "vision":
            logits, aux = self.forward(params, batch, remat=remat)
            lp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(lp, labels[:, None], axis=-1)
            return jnp.mean(nll) + 0.01 * aux

        if self._use_chunked_ce():
            # fused head+CE: the (tokens, vocab) logits never materialize
            # (dominant train activation for 256k-vocab archs — §Perf).
            hidden, aux = self._hidden_for_loss(params, batch, remat=remat)
            n = hidden.shape[0] * hidden.shape[1]
            nll = L.chunked_softmax_xent(
                hidden.reshape(n, cfg.d_model), self._head_weight(params),
                labels.reshape(n), cfg)
            mask = (labels.reshape(n) >= 0).astype(jnp.float32)
            loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
            return loss + 0.01 * aux

        logits, aux = self.forward(params, batch, remat=remat)
        mask = (labels >= 0).astype(jnp.float32)
        safe = jnp.maximum(labels, 0)
        lp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(lp, safe[..., None], axis=-1)[..., 0]
        loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        return loss + 0.01 * aux

    def _hidden_for_loss(self, params, batch, *, remat=False):
        """Final hidden states (pre-head) for the fused-CE path."""
        cfg = self.cfg
        if cfg.family == "audio":
            enc = self.encode(params, batch["enc_embeds"], remat=remat)
            y = L.embed(params["embed"], batch["dec_tokens"], cfg)
            y = y.astype(cfg.dtype)
            pos = L.sinusoidal_positions(y.shape[1], cfg.d_model)
            y = y + pos[None].astype(cfg.dtype)
            y, _, aux = T.run_stack(params["stack"], y, cfg, causal=True,
                                    enc_out=enc, remat=remat)
            return L.apply_norm(params["final_norm"], y, cfg), aux
        if "embeds" in batch:
            x = batch["embeds"].astype(cfg.dtype)
        else:
            x = L.embed(params["embed"], batch["tokens"], cfg).astype(cfg.dtype)
        hidden, _, aux = self._lm_hidden(
            params, x, positions=batch.get("positions"), remat=remat)
        return hidden, aux

    # --------------------------------------------------------------- serving
    def init_cache(self, batch: int, max_seq: int, enc_len: int = 0,
                   factory=None):
        return T.make_cache(self.cfg, batch, max_seq, enc_len=enc_len,
                            factory=factory)

    def init_paged_cache(self, batch: int, max_seq: int, *, page_size: int,
                         num_blocks: int, factory=None,
                         kv_dtype: str = "fp"):
        """Pool-backed slot cache: global-attention KV as physical pages
        (int8 rows + per-row scales under ``kv_dtype="int8"``), everything
        else dense (see ``transformer.make_paged_cache``)."""
        return T.make_paged_cache(self.cfg, batch, max_seq,
                                  page_size=page_size,
                                  num_blocks=num_blocks, factory=factory,
                                  kv_dtype=kv_dtype)

    def prefill(self, params, batch, max_seq: int):
        """Process the prompt; returns (logits_last, cache)."""
        cfg = self.cfg
        if cfg.family == "audio":
            enc = self.encode(params, batch["enc_embeds"])
            y = L.embed(params["embed"], batch["dec_tokens"], cfg)
            y = y.astype(cfg.dtype)
            pos = L.sinusoidal_positions(y.shape[1], cfg.d_model)
            y = y + pos[None].astype(cfg.dtype)
            cache = self.init_cache(y.shape[0], max_seq,
                                    enc_len=enc.shape[1])
            y, cache, _ = T.run_stack(
                params["stack"], y, cfg, causal=True, enc_out=enc,
                cache=cache, cache_index=jnp.int32(0), collect_state=True)
            y = L.apply_norm(params["final_norm"], y, cfg)
            logits = L.logits_head(params["embed"], params.get("head"),
                                   y[:, -1:], cfg)
            return logits, cache
        if "embeds" in batch:
            x = batch["embeds"].astype(cfg.dtype)
        else:
            x = L.embed(params["embed"], batch["tokens"], cfg).astype(cfg.dtype)
        cache = self.init_cache(x.shape[0], max_seq)
        logits, cache, _ = self._lm_trunk(
            params, x, positions=batch.get("positions"), cache=cache,
            cache_index=jnp.int32(0), collect_state=True)
        return logits[:, -1:], cache

    def prefill_one(self, params, tokens, length, max_seq: int):
        """Batch-1 prompt prefill against a FRESH dense cache (LM families
        only) — the admission primitive both cache layouts share.

        ``tokens`` (1, P) is right-padded; ``length`` (traced scalar) is
        the true prompt length.  Returns (logits at the last valid prompt
        position (1, 1, V), the batch-1 cache) — the caller scatters the
        cache into its persistent slot store (``scatter_cache_slot``).
        Pool-backed engines do NOT stage through here: they run
        ``prefill_suffix_paged``, which writes pages directly."""
        cfg = self.cfg
        if cfg.family in ("audio", "vision", "vlm") or cfg.mrope_sections:
            raise NotImplementedError(
                "per-slot prefill serves token-LM families "
                "(dense/moe/hybrid/ssm)")
        x = L.embed(params["embed"], tokens, cfg).astype(cfg.dtype)
        cache = self.init_cache(x.shape[0], max_seq)
        hidden, cache, _ = self._lm_hidden(
            params, x, cache=cache, cache_index=jnp.int32(0),
            collect_state=True)
        last = lax.dynamic_slice_in_dim(hidden, length - 1, 1, axis=1)
        logits = L.logits_head(params.get("embed"), params.get("head"),
                               last, cfg)
        return logits, cache

    def prefill_into_slot(self, params, full_cache, tokens, slot, length,
                          max_seq: int):
        """Per-slot prefill for continuous batching (LM families only).

        Runs ``prefill_one`` then scatters its cache into batch row
        ``slot`` of the persistent ``full_cache`` without touching any
        other slot.  Padding is exact under causal attention (pad tokens
        sit *after* every valid token, and their cache rows are
        overwritten by decode before any length mask admits them).

        Returns (logits at the last valid prompt position (1, 1, V),
        new_full_cache).  Admission cost is O(prompt), independent of how
        many other slots are mid-decode."""
        logits, cache = self.prefill_one(params, tokens, length, max_seq)
        return logits, T.scatter_cache_slot(full_cache, cache, slot)

    def prefill_suffix_paged(self, params, full_cache, tokens, slot,
                             offset, length, max_seq: int, block_tables,
                             write_tables):
        """Paged end-to-end prefill into slot ``slot`` of a pool-backed
        cache (LM families only): the prompt SUFFIX streams straight into
        the pool — no dense staging buffer, no commit-time copy.

        ``tokens`` (1, S) is the right-padded unmatched suffix;
        ``offset`` (traced scalar) counts the prefix tokens already
        sitting in shared pages (0 on a cold admission — then the suffix
        is the whole prompt); ``length`` (traced scalar) is the true
        suffix length.  ``block_tables`` (1, NB) maps every logical block
        of the request — shared prefix and fresh suffix — for the
        attention gather; ``write_tables`` (1, NB) names only the fresh
        blocks (sentinel elsewhere) so shared pages are never written.

        Global-attention K/V lands in its physical pages as the stack
        runs; dense per-slot state (SSM, local-window rings) rides a
        batch-1 part view and scatters into batch row ``slot`` at the
        end.  Returns (logits at the last valid suffix position (1,1,V),
        new_full_cache)."""
        cfg = self.cfg
        if cfg.family in ("audio", "vision", "vlm") or cfg.mrope_sections:
            raise NotImplementedError(
                "per-slot prefill serves token-LM families "
                "(dense/moe/hybrid/ssm)")
        x = L.embed(params["embed"], tokens, cfg).astype(cfg.dtype)
        view = T.combine_prefill_parts(
            full_cache, T.make_prefill_part(cfg, max_seq))
        hidden, new_view, _ = self._lm_hidden(
            params, x, cache=view, cache_index=jnp.asarray(offset, jnp.int32),
            collect_state=True, block_tables=block_tables,
            write_tables=write_tables)
        last = lax.dynamic_slice_in_dim(hidden, length - 1, 1, axis=1)
        logits = L.logits_head(params.get("embed"), params.get("head"),
                               last, cfg)
        return logits, T.merge_prefill_view(full_cache, new_view, slot)

    def decode_step(self, params, cache, tokens, cache_index,
                    positions=None, block_tables=None):
        """One decode step.  tokens: (B, S) — S = 1 for plain decode, or
        S = K+1 for a speculative-verify window (current token + K
        drafted tokens per slot, scored in one step).  Returns
        (logits, new_cache).

        ``cache_index`` is a scalar when all rows decode in lock-step, or a
        (B,) vector of per-slot positions for continuous batching (each
        slot then writes its own cache row(s) and attends under its own
        length mask — see ``layers.multi_head_attention``).
        ``block_tables`` maps logical to physical pages when ``cache`` is
        pool-backed (``transformer.make_paged_cache``)."""
        cfg = self.cfg
        x = L.embed(params["embed"], tokens, cfg).astype(cfg.dtype)
        if cfg.family == "audio":
            pos = L.sinusoidal_position_at(cache_index, cfg.d_model)
            x = x + pos[None, None].astype(cfg.dtype)
            x, cache, _ = T.run_stack(
                params["stack"], x, cfg, causal=True, cache=cache,
                cache_index=cache_index, collect_state=True)
            x = L.apply_norm(params["final_norm"], x, cfg)
            logits = L.logits_head(params["embed"], params.get("head"), x, cfg)
            return logits, cache
        logits, cache, _ = self._lm_trunk(
            params, x, positions=positions, cache=cache,
            cache_index=cache_index, collect_state=True,
            block_tables=block_tables)
        return logits, cache

    # ------------------------------------------------------------ input spec
    def input_specs(self, shape: ShapeConfig, *, batch_override: int = 0
                    ) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for every model input of this shape
        (weak-type-correct, shardable, no device allocation)."""
        cfg = self.cfg
        B = batch_override or shape.global_batch
        S = shape.seq_len
        i32, dt = jnp.int32, jnp.dtype(cfg.dtype)

        if cfg.family == "vision":
            return {"embeds": _sds((B, S, cfg.d_model), dt),
                    "labels": _sds((B,), i32)}

        if cfg.family == "audio":
            dec = max(S // AUDIO_DECODER_RATIO, 8)
            if shape.is_decode:
                cache = T.make_cache(cfg, B, S, enc_len=S, factory=_sds)
                return {"tokens": _sds((B, 1), i32),
                        "cache": cache,
                        "cache_index": _sds((), i32)}
            return {"enc_embeds": _sds((B, S, cfg.d_model), dt),
                    "dec_tokens": _sds((B, dec), i32),
                    "labels": _sds((B, dec), i32)}

        if shape.is_decode:
            spec = {"tokens": _sds((B, 1), i32),
                    "cache": T.make_cache(cfg, B, S, factory=_sds),
                    "cache_index": _sds((), i32)}
            if cfg.mrope_sections:
                spec["positions"] = _sds((3, B, 1), i32)
            return spec

        spec = {}
        if cfg.family == "vlm":
            spec["embeds"] = _sds((B, S, cfg.d_model), dt)
        else:
            spec["tokens"] = _sds((B, S), i32)
        spec["labels"] = _sds((B, S), i32)
        if cfg.mrope_sections:
            spec["positions"] = _sds((3, B, S), i32)
        return spec

    # --------------------------------------------------------------- counts
    def param_count(self, params) -> int:
        return sum(int(x.size) for x in jax.tree.leaves(params))


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
