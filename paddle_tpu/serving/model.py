"""Decode-model adapter: GPT functional core over a paged KV cache.

Bridges `models/gpt.py` (stacked-block functional GPT) to the serving
engine's two jitted entry points:

  prefill(params, cache, tokens [T], true_len, page_row [M])
      -> (cache', logits [V])
    Dense causal forward over one padded prompt bucket; per-layer K/V of
    every bucket position is scattered into the request's pages (padding
    positions land in the pool's trash page — see below) and the logits
    of the LAST REAL position come back for the first sampled token.

  decode(params, cache, tokens [S], positions [S], tables [S, M])
      -> (cache', logits [S, V])
    One token for every slot of the fixed-shape slot batch: embed at
    `positions`, per layer append K/V into the position's page, ragged
    paged attention over each slot's own history
    (ops/paged_attention.py), final LN + tied-embedding head.

Trash-page convention: the device pools carry ONE extra page at index
`num_pages` that absorbs every masked write — padded page-table entries
and inactive slots point at it, so the jitted step never needs a
data-dependent "skip this write" branch (writes are unconditional,
garbage lands in the trash page, reads are masked by ctx_len before
softmax). Page tables handed to these functions must therefore be
padded with `fill=num_pages`.

Numerical contract: bit-matches `models.gpt.gpt_forward` greedy decode
when scale factors are exact binary fractions (head_dim a power of two)
— the end-to-end parity test in tests/test_serving.py pins this.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..models.gpt import (GPTConfig, _causal_attention, _ln,
                          decoder_tail, init_gpt_params)
from ..ops.paged_attention import paged_attention_decode

__all__ = ["GPTDecodeModel"]


class GPTDecodeModel:
    """Serving adapter around the functional GPT core.

    The engine owns jit/donation/bucketing; everything here is pure."""

    def __init__(self, cfg: GPTConfig, params=None, seed: int = 0,
                 attn_impl: str | None = None):
        self.cfg = cfg
        self.params = params if params is not None \
            else init_gpt_params(cfg, seed)
        self.params = jax.tree_util.tree_map(jnp.asarray, self.params)
        self.head_dim = cfg.hidden_size // cfg.num_heads
        self.attn_impl = attn_impl  # None = auto (ops/autobench gate)
        # the engine caps admission at this (positions past wpe would
        # silently clip under jnp.take)
        self.max_positions = cfg.max_position_embeddings

    # -- checkpoint warm-start (paddle_tpu.checkpoint) ------------------
    def save_checkpoint(self, root: str, step: int | None = None) -> int:
        """Persist the param pytree through the checkpoint store
        (content-addressed chunks; repeated saves of a mostly-unchanged
        model dedup). Keys are tree paths, structure comes from the
        config at load time — no pickle anywhere."""
        import dataclasses
        from ..checkpoint import CheckpointStore
        leaves, _treedef = jax.tree_util.tree_flatten_with_path(
            self.params)
        arrays = {jax.tree_util.keystr(path): leaf
                  for path, leaf in leaves}
        return CheckpointStore(root).save(
            arrays, step=step,
            meta={"kind": "gpt-decode",
                  "cfg": dataclasses.asdict(self.cfg)})

    @classmethod
    def from_checkpoint(cls, root: str, step: int | None = None,
                        attn_impl: str | None = None,
                        cfg: "GPTConfig | None" = None) \
            -> "GPTDecodeModel":
        """Rebuild a decode model from a committed manifest: the config
        rides the manifest meta (overridable), a template pytree from it
        supplies the structure, and every leaf is restored by tree-path
        key. The serving engine's warm-start entry."""
        from ..checkpoint import CheckpointStore
        from ..models.gpt import GPTConfig
        store = CheckpointStore(root)
        arrays, meta = store.restore(step)
        if cfg is None:
            mcfg = (meta or {}).get("cfg")
            if not mcfg:
                raise ValueError(
                    f"manifest under {root} has no model config — pass "
                    f"cfg= explicitly")
            cfg = GPTConfig(**mcfg)
        model = cls(cfg, attn_impl=attn_impl)
        model.adopt_checkpoint(model._prepare_params(arrays, root))
        return model

    def read_checkpoint(self, root: str, step: int | None = None):
        """Disk + host->device phase of load_checkpoint: fetch the
        arrays AND build the complete replacement pytree
        (device-resident, dtype-cast against the live tree's
        structure) without touching live params. Engine.warm_start
        runs this off the step lock so serving overlaps both the read
        and the upload; the adopt_checkpoint flip is then a pure
        reference swap."""
        from ..checkpoint import CheckpointStore
        arrays, _meta = CheckpointStore(root).restore(step)
        return self._prepare_params(arrays, root)

    def adopt_checkpoint(self, prepared) -> "GPTDecodeModel":
        """Flip phase: adopt a pytree built by read_checkpoint /
        _prepare_params. One reference assignment — O(1) under the
        engine step lock, no disk, no host->device transfer."""
        self.params = prepared
        return self

    def load_checkpoint(self, root: str, step: int | None = None) \
            -> "GPTDecodeModel":
        """Swap this model's weights in place from a committed
        manifest (same structure required) — no throwaway model init,
        which matters when warm-starting a live engine on big
        configs."""
        return self.adopt_checkpoint(self.read_checkpoint(root, step))

    def _prepare_params(self, arrays: dict, root: str):
        """The replacement param pytree from tree-path-keyed arrays,
        using the CURRENT params as structural template (read-only;
        safe concurrent with a live engine decoding on the old
        tree)."""
        template, treedef = jax.tree_util.tree_flatten_with_path(
            self.params)
        leaves = []
        for path, tmpl in template:
            key = jax.tree_util.keystr(path)
            if key not in arrays:
                raise KeyError(f"checkpoint under {root} is missing "
                               f"param {key}")
            leaves.append(jnp.asarray(arrays[key],
                                      dtype=tmpl.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    # -- cache ---------------------------------------------------------
    def init_cache(self, num_pages: int, page_size: int):
        """[L, num_pages+1, ps, H, d] zero pools (last page = trash)."""
        cfg = self.cfg
        dt = jnp.dtype(cfg.amp_dtype) if cfg.amp_dtype else jnp.float32
        shape = (cfg.num_layers, num_pages + 1, page_size,
                 cfg.num_heads, self.head_dim)
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}

    def apply_defrag(self, cache, mapping: dict[int, int]):
        """Move live pages per defrag_plan's old->new mapping (host-side
        plan, one device gather per pool)."""
        if not mapping:
            return cache
        P = cache["k"].shape[1]
        perm = list(range(P))
        for old, new in mapping.items():
            perm[new] = old
        perm = jnp.asarray(perm, jnp.int32)
        return {"k": cache["k"][:, perm], "v": cache["v"][:, perm]}

    # -- layer math (mirrors models.gpt.gpt_block_fn) -------------------
    def _qkv(self, p, h):
        q = h @ p["wq"] + p["bq"]
        k = h @ p["wk"] + p["bk"]
        v = h @ p["wv"] + p["bv"]
        return q, k, v

    # (the post-attention tail — out-projection + residual + LN2 + FFN —
    # is models.gpt.decoder_tail: one source of truth with training, and
    # the serving decode path reuses the same autobench-gated fused
    # Pallas sub-blocks where they win)

    # -- prefill -------------------------------------------------------
    def prefill(self, params, cache, tokens, true_len, page_row):
        """tokens [T] int32 (padded bucket), true_len scalar int32,
        page_row [M] int32 (fill = trash). Returns (cache, logits [V])."""
        cfg = self.cfg
        H, d = cfg.num_heads, self.head_dim
        T = tokens.shape[0]
        ps = cache["k"].shape[2]
        n_pages = T // ps
        x = jnp.take(params["wte"], tokens, axis=0) \
            + params["wpe"][:T]                               # [T, D]

        def body(carry, xs):
            x, ck, cv = carry
            p, l = xs
            h = _ln(x, p["ln1_s"], p["ln1_b"], cfg.layer_norm_eps)
            q, k, v = self._qkv(p, h)
            kp = k.reshape(n_pages, ps, H, d).astype(ck.dtype)
            vp = v.reshape(n_pages, ps, H, d).astype(cv.dtype)
            ck = ck.at[l, page_row[:n_pages]].set(kp)
            cv = cv.at[l, page_row[:n_pages]].set(vp)
            # ONE source of truth for the dense math: the serving parity
            # contract (prefill == models.gpt forward, bit-for-bit) holds
            # by construction, not by a hand-mirrored copy
            a = _causal_attention(q[None], k[None], v[None], H,
                                  impl="xla")[0]
            x = decoder_tail(p, a, x, cfg)
            return (x, ck, cv), None

        L = cfg.num_layers
        (x, ck, cv), _ = jax.lax.scan(
            body, (x, cache["k"], cache["v"]),
            (params["blocks"], jnp.arange(L)))
        xlast = jax.lax.dynamic_index_in_dim(x, true_len - 1, 0,
                                             keepdims=False)
        xlast = _ln(xlast, params["lnf_s"], params["lnf_b"],
                    cfg.layer_norm_eps)
        logits = xlast.astype(jnp.float32) \
            @ params["wte"].T.astype(jnp.float32)
        return {"k": ck, "v": cv}, logits

    # -- tail prefill (shared-prefix admission) ------------------------
    def prefill_tail(self, params, cache, tokens, start, true_len,
                     page_row):
        """Prefill ONLY the unmatched tail of a prompt whose first
        `start` tokens (page-aligned) were found in the prefix cache
        with their KV already resident: tokens [T] int32 (padded tail
        bucket), start scalar int32 (page-aligned logical offset),
        true_len scalar int32 (real tail length), page_row [M] int32
        (matched + owned pages, fill = trash). Returns
        (cache, logits [V]) — the logits of the last real tail position.

        Numerics: each tail position is computed exactly like a decode
        step for that position — K/V scattered into its page, then
        ragged paged attention over the request's own history with
        ctx = position + 1 — so the greedy-parity contract the decode
        path pins (bit-match vs the dense forward) carries over to
        shared-prefix admissions unchanged."""
        import jax
        cfg = self.cfg
        H, d = cfg.num_heads, self.head_dim
        T = tokens.shape[0]
        ps = cache["k"].shape[2]
        n_pages = T // ps
        positions = start + jnp.arange(T, dtype=jnp.int32)
        x = jnp.take(params["wte"], tokens, axis=0) \
            + jnp.take(params["wpe"], positions, axis=0)       # [T, D]
        # every tail token shares the request's page row; per-token
        # causal masking rides the ctx lengths, as in decode
        tables = jnp.broadcast_to(page_row[None, :],
                                  (T, page_row.shape[0]))
        ctx = positions + 1
        # the tail's own pages: page_row[start//ps : start//ps + T//ps]
        tail_pages = jax.lax.dynamic_slice_in_dim(
            page_row, start // ps, n_pages)

        def body(carry, xs):
            x, ck, cv = carry
            p, l = xs
            h = _ln(x, p["ln1_s"], p["ln1_b"], cfg.layer_norm_eps)
            q, k, v = self._qkv(p, h)
            kp = k.reshape(n_pages, ps, H, d).astype(ck.dtype)
            vp = v.reshape(n_pages, ps, H, d).astype(cv.dtype)
            ck = ck.at[l, tail_pages].set(kp)
            cv = cv.at[l, tail_pages].set(vp)
            a = paged_attention_decode(
                q.reshape(T, H, d), ck, cv, tables, ctx, layer=l,
                scale=1.0 / math.sqrt(d), impl=self.attn_impl)
            x = decoder_tail(p, a.reshape(T, -1), x, cfg)
            return (x, ck, cv), None

        L = cfg.num_layers
        (x, ck, cv), _ = jax.lax.scan(
            body, (x, cache["k"], cache["v"]),
            (params["blocks"], jnp.arange(L)))
        xlast = jax.lax.dynamic_index_in_dim(x, true_len - 1, 0,
                                             keepdims=False)
        xlast = _ln(xlast, params["lnf_s"], params["lnf_b"],
                    cfg.layer_norm_eps)
        logits = xlast.astype(jnp.float32) \
            @ params["wte"].T.astype(jnp.float32)
        return {"k": ck, "v": cv}, logits

    def copy_pages(self, cache, src, dst):
        """Copy page contents src[i] -> dst[i] across every layer pool —
        the copy-on-write step for a full-prompt bootstrap admission
        (one small device gather/scatter per pool, outside jit)."""
        src = jnp.asarray(src, jnp.int32)
        dst = jnp.asarray(dst, jnp.int32)
        return {"k": cache["k"].at[:, dst].set(cache["k"][:, src]),
                "v": cache["v"].at[:, dst].set(cache["v"][:, src])}

    # -- decode --------------------------------------------------------
    def decode(self, params, cache, tokens, positions, tables):
        """tokens/positions [S] int32, tables [S, M] int32 (fill = trash;
        inactive slots = all-trash rows with position 0). Returns
        (cache, logits [S, V])."""
        cfg = self.cfg
        H, d = cfg.num_heads, self.head_dim
        S = tokens.shape[0]
        ps = cache["k"].shape[2]
        x = jnp.take(params["wte"], tokens, axis=0) \
            + jnp.take(params["wpe"], positions, axis=0)       # [S, D]
        page_of = jnp.take_along_axis(
            tables, (positions // ps)[:, None], axis=1)[:, 0]  # [S]
        off = positions % ps
        ctx = positions + 1

        def body(carry, xs):
            x, ck, cv = carry
            p, l = xs
            h = _ln(x, p["ln1_s"], p["ln1_b"], cfg.layer_norm_eps)
            q, k, v = self._qkv(p, h)
            ck = ck.at[l, page_of, off].set(
                k.reshape(S, H, d).astype(ck.dtype))
            cv = cv.at[l, page_of, off].set(
                v.reshape(S, H, d).astype(cv.dtype))
            a = paged_attention_decode(
                q.reshape(S, H, d), ck, cv, tables, ctx, layer=l,
                scale=1.0 / math.sqrt(d), impl=self.attn_impl)
            x = decoder_tail(p, a.reshape(S, -1), x, cfg)
            return (x, ck, cv), None

        L = cfg.num_layers
        (x, ck, cv), _ = jax.lax.scan(
            body, (x, cache["k"], cache["v"]),
            (params["blocks"], jnp.arange(L)))
        x = _ln(x, params["lnf_s"], params["lnf_b"], cfg.layer_norm_eps)
        logits = x.astype(jnp.float32) \
            @ params["wte"].T.astype(jnp.float32)
        return {"k": ck, "v": cv}, logits
