"""Decode-model adapters: a functional core over the serving cache.

`DecodeModel` is what the engine asks of a model; `GPTDecodeModel` (K and V
in two paged parts, nothing else), `HybridDecodeModel` (paged, per-slot
and tally parts), `LoopedDecodeModel` (K and V of every layer of every
PASS in two paged parts, and tallies), `LatentDecodeModel` (ONE latent
row a token a layer, no keys or values; two forms of attention) and
`WindowedDecodeModel` (the full layers' K and V in paged parts, the window
layers' in a ring of pages a slot) and `RecurrentDecodeModel` (a
recurrence's state and its convolution's taps per slot, the few attention
layers' shared K | V row paged) answer it.
Each adapter's bodies are drivers over its architecture's layer loop
(`GPTDecodeModel._layers`; `lfm2.apply_layers`; `ouro.apply_passes`;
`deepseek_v3.apply_layers`; `afmoe.apply_layers`; `jamba.apply_layers`):
they say how tokens
become `x`, where the attention state lands and what attends.

Trash-page convention: the device pools carry ONE extra page at index
`num_pages` that absorbs every masked write — padded page-table entries
and inactive slots point at it, so the jitted step never needs a
data-dependent "skip this write" branch (writes are unconditional,
garbage lands in the trash page, reads are masked by ctx_len before
softmax). Page tables handed to these functions must therefore be
padded with `fill=num_pages`.

Numerical contract (GPT): bit-matches `models.gpt.gpt_forward` greedy
decode when scale factors are exact binary fractions (head_dim a power of
two) — the end-to-end parity test in tests/test_serving.py pins this.
"""
from __future__ import annotations

import dataclasses
import logging
import math
import os

import jax
import jax.numpy as jnp

from ..models import afmoe as _afmoe
from ..models import deepseek_v3 as _dsv3
from ..models import jamba as _jamba
from ..models import layers as _layers
from ..models import lfm2 as _lfm2
from ..models import ouro as _ouro
from ..models.gpt import (GPTConfig, _causal_attention, _head, _ln,
                          decoder_tail, init_gpt_params)
from ..ops.paged_attention import (latent_row_width, paged_attention_decode,
                                   paged_attention_xla,
                                   paged_latent_attention_decode)
from ..ops.pallas_attention import on_tpu
from ..ops.selective_scan import SCAN_CHUNK

__all__ = ["DecodeModel", "GPTDecodeModel", "HybridDecodeModel",
           "LoopedDecodeModel", "LatentDecodeModel", "WindowedDecodeModel",
           "RecurrentDecodeModel"]

logger = logging.getLogger("paddle_tpu.serving.model")


class DecodeModel:
    """What `Engine` asks of a decode model: what it reads to serve is
    declared here (`Engine.warm_start` besides wants `GPTDecodeModel`'s
    checkpoint methods). The engine owns jit, donation and bucketing; the
    bodies are pure.

      cfg, params    the architecture's config, and the weight pytree handed
                     to every body (swapped whole on a warm start)
      max_positions  the longest sequence the model can address; the engine
                     caps admission at it
      init_cache(num_pages, page_size, num_slots) -> cache
      prefill(params, cache, tokens [T], true_len, page_row [M], slot)
          -> (cache', logits [V])   one padded prompt bucket: K/V of every
          position into the request's pages, the logits of the LAST REAL
          position for the first sampled token
      decode(params, cache, tokens [S], positions [S], tables [S, M])
          -> (cache', logits [S, V])   one token for every slot of the
          fixed-shape slot batch (inactive slots: all-trash rows, position 0)
      has_prefill_tail   the model can resume a prompt at a page boundary
          from the pages alone: prefill_tail(params, cache, tokens [T], start,
          true_len, page_row [M]) -> (cache', logits [V]). The prefix cache
          needs it; a model with per-slot state cannot have it.
      has_routing        the model records the experts chosen for every
          cached token: routing_of(cache, pages, length) (`Engine.submit(
          return_routing=True)`)

      passes             how many times a token runs the layers in one
          program: 1 unless the model loops (an attribute of the engine's
          prefill and decode spans)
      attn_forms         {"prefill": name, "decode": name} for a model
          whose prefill and decode compute attention by different programs
          of one function (attribute `attn` of those spans); empty
          otherwise
      residual_form      what carries a token through the layers where
          that is not one summed vector: `mhc<n>x<iters>` for n streams
          mixed by hyper-connections whose carry-over takes <iters>
          Sinkhorn iterations (attribute `residual` of those spans), and
          `residual_streams` = n (a gauge); "" and 1 otherwise. The
          streams are activations of the core's `apply_layers`: no part
          of the cache, nothing the engine moves
      window             the positions a model's window layers attend to,
          kept in a ring of `ring_pages(page_size)` pages a slot (a slot
          part); None without such layers. The engine reads it for its
          spans alone (`window_rows`, `past_window`, `window_pages_*`)
      scan_chunk         the positions a chunk of a model's recurrence
          scans in prefill, its state the carry from chunk to chunk; None
          without a recurrence. The engine reads it for its spans alone
          (`scan_len`, `scan_chunks`, `state_rows`)

    `cache'` has the keys, shapes and dtypes of `cache`: the engine donates
    it. The cache is a dict of device arrays, and `cache_kinds` says of each
    part what it is indexed by:

      "paged"  [layers of that kind, P+1, ps, ...]  per TOKEN, in pages under
               the one page table (attention K/V). `apply_defrag` and
               `copy_pages` move these and nothing else.
      "slot"   [layers of that kind, S, ...]  per SLOT, i.e. per sequence (a
               convolution's or a recurrence's state). Prefill of a request
               writes its slot's row whole, so a slot never reads its last
               tenant's; decode's row i is slot i. A window layer's ring is
               one too, [layers, S R + 1, ps, ...]: slot i's R pages are
               rows i R .. i R + R - 1 (then one trash page), and what the
               last tenant left there is masked by its position. Where a
               slot's state is a few rows (a convolution's three taps),
               the slot axis may lie further in, [layers, K-1, S, E], so
               that [S, E] are the tiled dimensions: the engine indexes no
               slot part, the model's bodies do.
      "tally"  counters the programs add to and only `Engine.stats` reads,
               through `tally_stats`: what a tally means is the model's.
    """

    cache_kinds: dict[str, str] = {}
    has_prefill_tail = False
    has_routing = False
    passes = 1
    attn_forms: dict[str, str] = {}
    residual_form = ""
    residual_streams = 1
    window: int | None = None
    scan_chunk: int | None = None

    def __init__(self, cfg, params, attn_impl: str | None = None):
        self.cfg = cfg
        self.params = jax.tree_util.tree_map(jnp.asarray, params)
        self.attn_impl = attn_impl      # None = auto (ops/autobench gate)
        # GPT: positions past wpe would silently clip under jnp.take
        self.max_positions = cfg.max_position_embeddings

    def init_cache(self, num_pages: int, page_size: int, num_slots: int = 0):
        raise NotImplementedError

    def prefill(self, params, cache, tokens, true_len, page_row, slot=None):
        raise NotImplementedError

    def decode(self, params, cache, tokens, positions, tables):
        raise NotImplementedError

    def parts_of(self, kind: str) -> tuple:
        return tuple(n for n, k in self.cache_kinds.items() if k == kind)

    def ring_pages(self, page_size: int) -> int:
        """Pages of a slot's ring: the window in pages and one more, so
        that the page being written never holds a position still read."""
        return -(-self.window // page_size) + 1

    @property
    def slot_state(self) -> bool:
        """Whether some state is kept per sequence and not per token."""
        return bool(self.parts_of("slot"))

    def tally_stats(self, now: dict, delta: dict, steps: int) -> dict:
        """What `Engine.stats()` reports of the tally parts: `now` holds
        each part as read from the device (numpy, int64 or float64),
        `delta` what was added since the last read, `steps` the decode
        steps run since then. Keys go into `stats()` as they are."""
        return {}

    def cache_bytes(self, cache) -> dict:
        """Bytes held, by kind of part."""
        out = {"paged": 0, "slot": 0, "tally": 0}
        for name, kind in self.cache_kinds.items():
            out[kind] += int(cache[name].nbytes)
        return out

    def apply_defrag(self, cache, mapping: dict[int, int]):
        """Move live pages per defrag_plan's old->new mapping (host-side
        plan, one device gather per paged part; per-slot state stays
        where it is: slots do not move)."""
        if not mapping:
            return cache
        paged = self.parts_of("paged")
        P = cache[paged[0]].shape[1]
        perm = list(range(P))
        for old, new in mapping.items():
            perm[new] = old
        perm = jnp.asarray(perm, jnp.int32)
        return {**cache, **{n: cache[n][:, perm] for n in paged}}

    def copy_pages(self, cache, src, dst):
        """Copy page contents src[i] -> dst[i] in every paged part: the
        copy-on-write step for a full-prompt bootstrap admission (one
        small device gather/scatter per part, outside jit)."""
        src = jnp.asarray(src, jnp.int32)
        dst = jnp.asarray(dst, jnp.int32)
        return {**cache, **{n: cache[n].at[:, dst].set(cache[n][:, src])
                            for n in self.parts_of("paged")}}


class GPTDecodeModel(DecodeModel):
    """Serving adapter around the functional GPT core (`models/gpt.py`):
    `prefill`, `prefill_tail` and `decode` are drivers over `_layers`."""

    cache_kinds = {"k": "paged", "v": "paged"}
    has_prefill_tail = True

    def __init__(self, cfg: GPTConfig, params=None, seed: int = 0,
                 attn_impl: str | None = None):
        super().__init__(cfg, params if params is not None
                         else init_gpt_params(cfg, seed), attn_impl)
        self.head_dim = cfg.hidden_size // cfg.num_heads
        # The layer loop scans over the stacked blocks, so a layer's `wo`,
        # `w_up` and `w_down` reach `decoder_tail` as slices of the stack.
        # XLA reads such a slice in place inside the product it fuses it
        # with; in front of a Mosaic call it is a copy, the weight stream
        # itself with the kernel serial behind it (75 MB a layer at GPT-3
        # XL: 1.8 ms of a 12.2 ms decode step on a v5e, and the chain is
        # ahead at every prefill bucket too; PERF.md, PR 35). So the
        # serving bodies take the tail's composed chain, as the trainer
        # does on a mesh; training on one chip keeps `cfg.fused_blocks`.
        self._tail_cfg = dataclasses.replace(cfg, fused_blocks=False)
        if cfg.fused_blocks:
            logger.info(
                "fused_blocks is off in the serving bodies: a scan's slice "
                "of the stacked weights in front of the fused decoder-tail "
                "kernels (ops/pallas_block.py) is a copy; the composed XLA "
                "tail runs instead")

    # -- checkpoint warm-start (paddle_tpu.checkpoint) ------------------
    def save_checkpoint(self, root: str, step: int | None = None) -> int:
        """Persist the param pytree through the checkpoint store
        (content-addressed chunks; repeated saves of a mostly-unchanged
        model dedup). Keys are tree paths, structure comes from the
        config at load time — no pickle anywhere."""
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
    def init_cache(self, num_pages: int, page_size: int,
                   num_slots: int = 0):
        """[L, num_pages+1, ps, H, d] zero pools (last page = trash);
        nothing is kept per slot."""
        cfg = self.cfg
        dt = jnp.dtype(cfg.amp_dtype) if cfg.amp_dtype else jnp.float32
        shape = (cfg.num_layers, num_pages + 1, page_size,
                 cfg.num_heads, self.head_dim)
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}

    # -- the layer loop ------------------------------------------------
    def _layers(self, params, cache, x, write, attend):
        """x [N, D] through every block, in one scan over the stacked
        blocks. Per layer l: LN1, q/k/v, `write(ck, cv, l, k, v) -> (ck,
        cv)` puts the layer's K and V [N, D] into the stacked pools,
        `attend(q, k, v, ck, cv, l) -> [N, D]`, then models.gpt's
        post-attention tail (out-projection + residual + LN2 + FFN: one
        source of truth with training; here always its composed chain,
        which XLA fuses with the scan's slice of the weights, see
        `__init__`). Returns (x, cache)."""
        cfg = self.cfg

        def body(carry, xs):
            x, ck, cv = carry
            p, l = xs
            h = _ln(x, p["ln1_s"], p["ln1_b"], cfg.layer_norm_eps)
            q = h @ p["wq"] + p["bq"]
            k = h @ p["wk"] + p["bk"]
            v = h @ p["wv"] + p["bv"]
            ck, cv = write(ck, cv, l, k, v)
            x = decoder_tail(p, attend(q, k, v, ck, cv, l), x,
                             self._tail_cfg)
            return (x, ck, cv), None

        (x, ck, cv), _ = jax.lax.scan(
            body, (x, cache["k"], cache["v"]),
            (params["blocks"], jnp.arange(cfg.num_layers)))
        return x, {"k": ck, "v": cv}

    def _write_pages(self, cache, T, pages):
        """`write` of a bucket of T positions into whole pages. `pages()`
        [T // ps] names them and is asked inside the loop: that is where
        `prefill` has always sliced its page row, and the lowered programs
        are held byte for byte (scripts/lowered_serving_programs.py)."""
        ps = cache["k"].shape[2]
        shape = (T // ps, ps, self.cfg.num_heads, self.head_dim)

        def write(ck, cv, l, k, v):
            kp = k.reshape(shape).astype(ck.dtype)
            vp = v.reshape(shape).astype(cv.dtype)
            return ck.at[l, pages()].set(kp), cv.at[l, pages()].set(vp)
        return write

    def _paged(self, tables, ctx):
        """`attend` of N single positions over their own cached history:
        ragged paged attention (ops/paged_attention.py), tables [N, M], ctx
        [N] tokens visible to each (its own K/V, just written, included)."""
        H, d = self.cfg.num_heads, self.head_dim

        def attend(q, k, v, ck, cv, l):
            N = q.shape[0]
            a = paged_attention_decode(
                q.reshape(N, H, d), ck, cv, tables, ctx, layer=l,
                scale=1.0 / math.sqrt(d), impl=self.attn_impl)
            return a.reshape(N, -1)
        return attend

    def _last_logits(self, params, x, true_len):
        """Logits [V] of a bucket's last real position."""
        xlast = jax.lax.dynamic_index_in_dim(x, true_len - 1, 0,
                                             keepdims=False)
        return _head(params, xlast, self.cfg)

    # -- prefill -------------------------------------------------------
    def prefill(self, params, cache, tokens, true_len, page_row,
                slot=None):
        """tokens [T] int32 (padded bucket), true_len scalar int32,
        page_row [M] int32 (fill = trash; padding positions land in the
        trash page); `slot` is not used (no part is per slot). Dense
        causal forward. Returns (cache, logits [V])."""
        T = tokens.shape[0]
        n_pages = T // cache["k"].shape[2]
        x = jnp.take(params["wte"], tokens, axis=0) \
            + params["wpe"][:T]                               # [T, D]

        def attend(q, k, v, ck, cv, l):
            # ONE source of truth for the dense math: the serving parity
            # contract (prefill == models.gpt forward, bit-for-bit) holds
            # by construction, not by a hand-mirrored copy
            return _causal_attention(q[None], k[None], v[None],
                                     self.cfg.num_heads, impl="xla")[0]

        x, cache = self._layers(
            params, cache, x,
            self._write_pages(cache, T, lambda: page_row[:n_pages]), attend)
        return cache, self._last_logits(params, x, true_len)

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
        T = tokens.shape[0]
        ps = cache["k"].shape[2]
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
            page_row, start // ps, T // ps)
        x, cache = self._layers(
            params, cache, x,
            self._write_pages(cache, T, lambda: tail_pages),
            self._paged(tables, ctx))
        return cache, self._last_logits(params, x, true_len)

    # -- decode --------------------------------------------------------
    def decode(self, params, cache, tokens, positions, tables):
        """tokens/positions [S] int32, tables [S, M] int32 (fill = trash;
        inactive slots = all-trash rows with position 0): embed at
        `positions`, append each slot's K/V at its position's page and
        offset, attend over its own history. Returns (cache, logits
        [S, V])."""
        H, d = self.cfg.num_heads, self.head_dim
        S = tokens.shape[0]
        ps = cache["k"].shape[2]
        x = jnp.take(params["wte"], tokens, axis=0) \
            + jnp.take(params["wpe"], positions, axis=0)       # [S, D]
        page_of = jnp.take_along_axis(
            tables, (positions // ps)[:, None], axis=1)[:, 0]  # [S]
        off = positions % ps
        ctx = positions + 1

        def write(ck, cv, l, k, v):
            ck = ck.at[l, page_of, off].set(
                k.reshape(S, H, d).astype(ck.dtype))
            cv = cv.at[l, page_of, off].set(
                v.reshape(S, H, d).astype(cv.dtype))
            return ck, cv

        x, cache = self._layers(params, cache, x, write,
                                self._paged(tables, ctx))
        return cache, _head(params, x, self.cfg)


class _ExpertRecords:
    """What an adapter whose model routes experts keeps of the routing,
    beside its attention state (mixed into `HybridDecodeModel` and
    `LatentDecodeModel`; `cfg` gives `num_experts`, `num_experts_per_tok`,
    `num_moe_layers`). `routing` is a paged part: the experts chosen for
    every cached token, [1, P+1, ps x expert layers x k] (what
    `Engine.submit(return_routing=True)` hands back; a page's tokens lie
    side by side in one minor dimension, because the chip turns a minor
    dimension of 48 round and then copies the whole part twice a step, PR
    26); int8 up to 127 experts, int16 beyond. `expert_tokens` and
    `expert_touched` are tallies [expert layers, E]: token-expert pairs of
    real tokens, and decode steps in which a live slot's token reached the
    expert."""

    has_routing = True
    _expert_kinds = {"routing": "paged", "expert_tokens": "tally",
                     "expert_touched": "tally"}

    def _expert_parts(self, num_pages: int, page_size: int) -> dict:
        cfg = self.cfg
        Lm, E, k = cfg.num_moe_layers, cfg.num_experts, \
            cfg.num_experts_per_tok
        return {
            # the paged axis second, as in every paged part
            "routing": jnp.zeros((1, num_pages + 1, page_size * Lm * k),
                                 jnp.int8 if E <= 127 else jnp.int16),
            "expert_tokens": jnp.zeros((Lm, E), jnp.int32),
            "expert_touched": jnp.zeros((Lm, E), jnp.int32)}

    def routing_of(self, cache, pages, length: int):
        """The experts chosen at the first `length` cached positions of a
        request that holds `pages`: [length, expert layers, k] (numpy).
        Give `pages` padded to one length (the engine's page row), so that
        the gather is one program."""
        import numpy as np
        got = np.asarray(cache["routing"][0, jnp.asarray(pages, jnp.int32)])
        k = self.cfg.num_experts_per_tok
        return got.reshape(-1, self.cfg.num_moe_layers, k)[:length]

    def tally_stats(self, now, delta, steps):
        """`expert_tokens` / `expert_touched` since the engine began and,
        over what was added since the last read, the busiest expert of a
        layer over the layer's mean (mean over the layers; 1.0 is an even
        load) and the share of the experts a decode step reached."""
        pairs, touched = delta["expert_tokens"], delta["expert_touched"]
        mean = pairs.mean(axis=1)
        out = {"expert_tokens": now["expert_tokens"].tolist(),
               "expert_touched": now["expert_touched"].tolist(),
               "expert_load_max_over_mean": None,
               "experts_touched_share": None}
        if (mean > 0).all():
            out["expert_load_max_over_mean"] = float(
                (pairs.max(axis=1) / mean).mean())
        if steps > 0:
            out["experts_touched_share"] = float(
                touched.sum() / (steps * touched.size))
        return out

    def _pairs(self, sel, live):
        """Token-expert pairs [expert layers, E] of the tokens marked
        `live` [N]; sel [expert layers, N, k]."""
        hot = jax.nn.one_hot(sel, self.cfg.num_experts, dtype=jnp.int32)
        return jnp.sum(hot * live[None, :, None, None].astype(jnp.int32),
                       axis=(1, 2))

    def _routes(self, sel, dtype):
        """sel [expert layers, N, k] as rows of the `routing` part."""
        return jnp.moveaxis(sel, 0, 1).reshape(sel.shape[1], -1) \
            .astype(dtype)

    def _recorded_prefill(self, cache, sel, pages, real) -> dict:
        """The three parts after a prefill that chose sel [expert layers,
        T, k] for a bucket whose positions `real` [T] are the prompt's and
        whose pages are `pages` [T // ps]."""
        rt = cache["routing"]
        return {"routing": rt.at[0, pages].set(
                    self._routes(sel, rt.dtype).reshape(pages.shape[0], -1)),
                "expert_tokens": cache["expert_tokens"]
                + self._pairs(sel, real),
                "expert_touched": cache["expert_touched"]}

    def _recorded_decode(self, cache, sel, page_of, off, live) -> dict:
        """The three parts after a decode step that chose sel [expert
        layers, S, k]; slot i's token lies at offset off[i] of page
        page_of[i], and counts if live[i]."""
        hit = self._pairs(sel, live)
        rt = cache["routing"]
        routes = self._routes(sel, rt.dtype)                    # [S, Lm k]
        lanes = off[:, None] * routes.shape[1] \
            + jnp.arange(routes.shape[1], dtype=jnp.int32)
        return {"routing": rt.at[0, page_of[:, None], lanes].set(routes),
                "expert_tokens": cache["expert_tokens"] + hit,
                "expert_touched": cache["expert_touched"]
                + (hit > 0).astype(jnp.int32)}


class HybridDecodeModel(_ExpertRecords, DecodeModel):
    """Serving adapter around `models/lfm2.py`: layers of two kinds, two
    kinds of state. Attention layers keep K and V per token in ONE fused
    paged part `kv` [attention layers, P+1, ps, Hkv, 2d] (K | V side by
    side: a head of 64 alone is a poor minor dimension on the chip, see
    ops/paged_attention.py). Convolution layers keep the last K-1 gated
    inputs per slot in `conv` [conv layers, S, K-1, D]. The routing part
    (48 bytes a token here, a minor dimension of 768) and the two expert
    tallies are `_ExpertRecords`'.

    The three bodies here are drivers over `lfm2.apply_layers`: they
    differ in what attention does with its state, nothing else."""

    cache_kinds = {"kv": "paged", "conv": "slot",
                   **_ExpertRecords._expert_kinds}

    def __init__(self, cfg: "_lfm2.LFM2Config", params=None, seed: int = 0,
                 attn_impl: str | None = None):
        # no position table to run past: RoPE; max_positions is the
        # config's own ceiling
        super().__init__(cfg, params if params is not None
                         else _lfm2.init_params(cfg, seed), attn_impl)
        self.head_dim = cfg.head_dim

    # -- cache ---------------------------------------------------------
    def init_cache(self, num_pages: int, page_size: int, num_slots: int):
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        La, Lc = cfg.layers_of(_lfm2.ATTN), cfg.layers_of(_lfm2.CONV)
        return {
            "kv": jnp.zeros((La, num_pages + 1, page_size,
                             cfg.num_key_value_heads, 2 * cfg.head_dim),
                            dt),
            "conv": jnp.zeros((Lc, num_slots, cfg.conv_L_cache - 1,
                               cfg.hidden_size), dt),
            **self._expert_parts(num_pages, page_size)}

    # -- prefill -------------------------------------------------------
    def prefill(self, params, cache, tokens, true_len, page_row, slot):
        """tokens [T] int32 (padded bucket), true_len and slot scalar
        int32, page_row [M] int32 (fill = trash). Returns (cache,
        logits [V]) of the last real position. The slot's convolution
        state is written whole, from positions true_len-2 and true_len-1
        (zeros before the prompt's start)."""
        cfg = self.cfg
        T = tokens.shape[0]
        ps = cache["kv"].shape[2]
        n_pages = T // ps
        pages = page_row[:n_pages]
        x = jnp.take(params["embed"], tokens, axis=0)[None]     # [1, T, D]
        positions = jnp.arange(T, dtype=jnp.int32)[None]
        scale = 1.0 / math.sqrt(cfg.head_dim)

        def attend(q, k, v, state):
            pool, i = state
            kv = jnp.concatenate([k, v], axis=-1)[0]            # [T, Hkv, 2d]
            pool = pool.at[i, pages].set(
                kv.reshape((n_pages, ps) + kv.shape[1:]).astype(pool.dtype))
            return _layers.dense_causal_attention(q, k, v, scale), (pool, i)

        x, conv, pool, sel = _lfm2.apply_layers(
            cfg, params, x, positions,
            _lfm2.zero_conv_state(cfg, 1, cache["conv"].dtype), attend,
            cache["kv"], lengths=jnp.reshape(true_len, (1,)))
        xlast = jax.lax.dynamic_index_in_dim(x[0], true_len - 1, 0,
                                             keepdims=False)
        logits = _lfm2.head_logits(params, xlast, cfg)
        real = jnp.arange(T, dtype=jnp.int32) < true_len
        return {
            "kv": pool,
            "conv": jax.lax.dynamic_update_slice_in_dim(
                cache["conv"], conv, slot, axis=1),
            **self._recorded_prefill(cache, sel, pages, real)}, logits

    # -- decode --------------------------------------------------------
    def decode(self, params, cache, tokens, positions, tables):
        """tokens/positions [S] int32, tables [S, M] int32 (fill = trash;
        inactive slots = all-trash rows with position 0). Row i is slot
        i. Returns (cache, logits [S, V])."""
        cfg = self.cfg
        S = tokens.shape[0]
        ps, trash = cache["kv"].shape[2], cache["kv"].shape[1] - 1
        x = jnp.take(params["embed"], tokens, axis=0)[:, None]  # [S, 1, D]
        page_of = jnp.take_along_axis(
            tables, (positions // ps)[:, None], axis=1)[:, 0]
        off = positions % ps
        ctx = positions + 1
        scale = 1.0 / math.sqrt(cfg.head_dim)

        def attend(q, k, v, state):
            pool, i = state
            kv = jnp.concatenate([k, v], axis=-1)[:, 0]         # [S, Hkv, 2d]
            pool = pool.at[i, page_of, off].set(kv.astype(pool.dtype))
            a = paged_attention_decode(
                q[:, 0], pool, None, tables, ctx, layer=i, scale=scale,
                impl=self.attn_impl)
            return a.reshape(S, 1, -1), (pool, i)

        x, conv, pool, sel = _lfm2.apply_layers(
            cfg, params, x, positions[:, None], cache["conv"], attend,
            cache["kv"])
        logits = _lfm2.head_logits(params, x[:, 0], cfg)
        return {"kv": pool, "conv": conv,
                **self._recorded_decode(cache, sel, page_of, off,
                                        page_of != trash)}, logits


class LoopedDecodeModel(DecodeModel):
    """Serving adapter around `models/ouro.py`: ONE stack of layers run
    `total_ut_steps` times a token. Pass t attends over pass t's K/V only,
    so a token owns a K/V row for every layer of every pass: two paged
    parts `k`, `v` [passes x layers, P+1, ps, Hkv, d], row `ouro.cache_row`
    = t L + l, under the ONE page table (a page id means the same `ps`
    positions in every pass and layer). At Ouro-2.6B's sizes that is 1.5
    MiB a token: the pool, not the slots, sets what a chip holds, and a
    copy of it does not fit, so it stays one donated buffer through both
    of `ouro.apply_passes`'s loops. Tallies, by pass: `loop_passes` (tokens
    of real prompt positions and live slots that ran the pass) and
    `exit_mass` (the sum over those tokens of the exit distribution's
    p_t). Nothing is kept per slot.

    `prefill` and `decode` are drivers over `ouro.apply_passes`: they
    differ in where a pass's K/V land and what its q attends over."""

    cache_kinds = {"k": "paged", "v": "paged", "loop_passes": "tally",
                   "exit_mass": "tally"}

    def __init__(self, cfg: "_ouro.OuroConfig", params=None, seed: int = 0,
                 attn_impl: str | None = None):
        super().__init__(cfg, params if params is not None
                         else _ouro.init_params(cfg, seed), attn_impl)
        self.passes = cfg.total_ut_steps

    def init_cache(self, num_pages: int, page_size: int,
                   num_slots: int = 0):
        cfg = self.cfg
        shape = (cfg.cache_rows, num_pages + 1, page_size,
                 cfg.num_key_value_heads, cfg.head_dim)
        dt = jnp.dtype(cfg.dtype)
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt),
                "loop_passes": jnp.zeros((cfg.total_ut_steps,), jnp.int32),
                "exit_mass": jnp.zeros((cfg.total_ut_steps,), jnp.float32)}

    def tally_stats(self, now, delta, steps):
        """`loop_passes` since the engine began and, over what was added
        since the last read: passes run a token (tokens that ran pass 0
        are all the tokens fed) and each pass's share of the exit mass."""
        ran, mass = delta["loop_passes"], delta["exit_mass"]
        return {"loop_passes": now["loop_passes"].tolist(),
                "loop_passes_per_token":
                    float(ran.sum() / ran[0]) if ran[0] > 0 else None,
                "exit_mass_share":
                    (mass / mass.sum()).tolist() if mass.sum() > 0 else None}

    def _tallied(self, cache, lam, live):
        """The tally parts after a program whose loop gave lam [passes, N]
        for N tokens of which `live` [N] are real."""
        n = jnp.sum(live.astype(jnp.int32))
        p = _ouro.exit_distribution(lam) * live[None, :].astype(jnp.float32)
        return {"loop_passes": cache["loop_passes"]
                + jnp.full((lam.shape[0],), n, jnp.int32),
                "exit_mass": cache["exit_mass"] + jnp.sum(p, axis=1)}

    # -- prefill -------------------------------------------------------
    def prefill(self, params, cache, tokens, true_len, page_row,
                slot=None):
        """tokens [T] int32 (padded bucket), true_len scalar int32,
        page_row [M] int32 (fill = trash); `slot` is not used. Dense
        causal attention within each pass; every pass's K/V of every
        position into the request's pages. Returns (cache, logits [V]) of
        the last real position."""
        cfg = self.cfg
        T = tokens.shape[0]
        ps = cache["k"].shape[2]
        pages = page_row[:T // ps]
        x = jnp.take(params["embed"], tokens, axis=0)[None]     # [1, T, D]
        positions = jnp.arange(T, dtype=jnp.int32)[None]
        scale = 1.0 / math.sqrt(cfg.head_dim)

        def attend(q, k, v, state, row):
            ck, cv = state
            shape = (T // ps, ps) + k.shape[2:]
            ck = ck.at[row, pages].set(k[0].reshape(shape).astype(ck.dtype))
            cv = cv.at[row, pages].set(v[0].reshape(shape).astype(cv.dtype))
            return _layers.dense_causal_attention(q, k, v, scale), (ck, cv)

        x, lam, (ck, cv) = _ouro.apply_passes(
            cfg, params, x, positions, attend, (cache["k"], cache["v"]))
        xlast = jax.lax.dynamic_index_in_dim(x[0], true_len - 1, 0,
                                             keepdims=False)
        real = jnp.arange(T, dtype=jnp.int32) < true_len
        return {"k": ck, "v": cv, **self._tallied(cache, lam[:, 0], real)}, \
            _ouro.head_logits(params, xlast)

    # -- decode --------------------------------------------------------
    def decode(self, params, cache, tokens, positions, tables):
        """tokens/positions [S] int32, tables [S, M] int32 (fill = trash;
        inactive slots = all-trash rows with position 0). In each pass and
        layer: the slot's K/V to its position's page and offset in that
        pass's row, then ragged paged attention over that row. Returns
        (cache, logits [S, V])."""
        cfg = self.cfg
        S = tokens.shape[0]
        ps, trash = cache["k"].shape[2], cache["k"].shape[1] - 1
        # the slot batch as ONE row of S positions, each with its own
        # position and history: the layers' products are then [S, D] x [D, .]
        x = jnp.take(params["embed"], tokens, axis=0)[None]     # [1, S, D]
        page_of = jnp.take_along_axis(
            tables, (positions // ps)[:, None], axis=1)[:, 0]
        off = positions % ps
        ctx = positions + 1
        scale = 1.0 / math.sqrt(cfg.head_dim)

        def attend(q, k, v, state, row):
            ck, cv = state
            ck = ck.at[row, page_of, off].set(k[0].astype(ck.dtype))
            cv = cv.at[row, page_of, off].set(v[0].astype(cv.dtype))
            a = paged_attention_decode(
                q[0], ck, cv, tables, ctx, layer=row, scale=scale,
                impl=self.attn_impl)
            return a.reshape(1, S, -1), (ck, cv)

        x, lam, (ck, cv) = _ouro.apply_passes(
            cfg, params, x, positions[None], attend,
            (cache["k"], cache["v"]))
        live = page_of != trash
        return {"k": ck, "v": cv, **self._tallied(cache, lam[:, 0], live)}, \
            _ouro.head_logits(params, x[0])


class LatentDecodeModel(_ExpertRecords, DecodeModel):
    """Serving adapter around `models/deepseek_v3.py`: multi-head latent
    attention. A token's state in a layer is ONE row [c | kr] of
    `cfg.latent_width` numbers (576 at the published sizes, 1,152 bytes in
    bf16, against 20,480 for the keys and values of its 32 heads), with no
    head axis: the paged part `latent` [layers, P+1, ps, W], W the width
    padded to whole 128-lane registers (`ops/paged_attention.py::
    latent_row_width`: 640; the lanes past the width stay zero). No key and
    no value is ever stored. The routing part (int16: 128 experts) and the
    expert tallies are `_ExpertRecords`'. Nothing is kept per slot.

    `prefill` and `decode` are drivers over `deepseek_v3.apply_layers` and
    run the two FORMS of latent attention (`attn_forms`): prefill the
    expanded one (keys and values of every head built from c, products 192
    wide over T^2 pairs) and writes the rows; decode the absorbed one (the
    key up-projection folded into the query, a query of 576 against the
    cached row, the value the row's own first 512 numbers: `ops/
    paged_attention.py::paged_latent_attention_decode`).

    A configuration whose residual is several streams (`cfg.hc_mult`,
    `model_type: xing4_0`) is served by the same two drivers: the streams
    are activations inside `apply_layers` and leave nothing in the cache;
    `residual_form` names them on the engine's spans."""

    cache_kinds = {"latent": "paged", **_ExpertRecords._expert_kinds}
    attn_forms = {"prefill": "expanded", "decode": "absorbed"}

    def __init__(self, cfg: "_dsv3.DeepseekV3Config", params=None,
                 seed: int = 0, attn_impl: str | None = None):
        super().__init__(cfg, params if params is not None
                         else _dsv3.init_params(cfg, seed), attn_impl)
        if cfg.hc_mult:
            self.residual_streams = cfg.hc_mult
            self.residual_form = f"mhc{cfg.hc_mult}x{cfg.hc_sinkhorn_iters}"

    def init_cache(self, num_pages: int, page_size: int,
                   num_slots: int = 0):
        cfg = self.cfg
        return {"latent": jnp.zeros(
                    (cfg.num_hidden_layers, num_pages + 1, page_size,
                     latent_row_width(cfg.latent_width)),
                    jnp.dtype(cfg.dtype)),
                **self._expert_parts(num_pages, page_size)}

    @staticmethod
    def _rows(c, kr, pool):
        """[c | kr] of N tokens as rows of the pool: [N, W], zeros in the
        lanes past the latent width."""
        row = jnp.concatenate([c, kr], axis=-1)
        pad = pool.shape[-1] - row.shape[-1]
        return jnp.pad(row, ((0, 0), (0, pad))).astype(pool.dtype)

    # -- prefill: the expanded form ----------------------------------------
    def prefill(self, params, cache, tokens, true_len, page_row,
                slot=None):
        """tokens [T] int32 (padded bucket), true_len scalar int32,
        page_row [M] int32 (fill = trash); `slot` is not used. The rows of
        every position into the request's pages, causal attention over the
        bucket in the expanded form. Returns (cache, logits [V]) of the
        last real position."""
        cfg = self.cfg
        T = tokens.shape[0]
        ps = cache["latent"].shape[2]
        pages = page_row[:T // ps]
        x = jnp.take(params["embed"], tokens, axis=0)[None]     # [1, T, D]
        positions = jnp.arange(T, dtype=jnp.int32)[None]

        def attend(p, q_nope, q_rope, c, kr, pool, l):
            rows = self._rows(c[0], kr[0], pool)
            pool = pool.at[l, pages].set(rows.reshape(T // ps, ps, -1))
            return _dsv3.expanded_attention(p, q_nope, q_rope, c, kr,
                                            cfg.softmax_scale), pool

        x, pool, sel = _dsv3.apply_layers(cfg, params, x, positions, attend,
                                          cache["latent"])
        xlast = jax.lax.dynamic_index_in_dim(x[0], true_len - 1, 0,
                                             keepdims=False)
        real = jnp.arange(T, dtype=jnp.int32) < true_len
        return {"latent": pool,
                **self._recorded_prefill(cache, sel, pages, real)}, \
            _dsv3.head_logits(params, xlast, cfg)

    # -- decode: the absorbed form -----------------------------------------
    def decode(self, params, cache, tokens, positions, tables):
        """tokens/positions [S] int32, tables [S, M] int32 (fill = trash;
        inactive slots = all-trash rows with position 0). In each layer:
        the slot's row to its position's page and offset, then the absorbed
        query over the slot's cached rows. Returns (cache, logits
        [S, V])."""
        cfg = self.cfg
        ps, trash = cache["latent"].shape[2], cache["latent"].shape[1] - 1
        # the slot batch as ONE row of S positions, each with its own
        # position and history: the layers' products are [S, D] x [D, .]
        x = jnp.take(params["embed"], tokens, axis=0)[None]     # [1, S, D]
        page_of = jnp.take_along_axis(
            tables, (positions // ps)[:, None], axis=1)[:, 0]
        off = positions % ps
        ctx = positions + 1

        def attend(p, q_nope, q_rope, c, kr, pool, l):
            pool = pool.at[l, page_of, off].set(
                self._rows(c[0], kr[0], pool))
            q = _dsv3.absorb_query(p, q_nope[0], q_rope[0])   # [S, H, 576]
            o = paged_latent_attention_decode(
                q.astype(pool.dtype), pool, tables, ctx,
                value_width=cfg.kv_lora_rank, scale=cfg.softmax_scale,
                layer=l, impl=self.attn_impl)
            return _dsv3.expand_value(p, o)[None], pool

        x, pool, sel = _dsv3.apply_layers(cfg, params, x, positions[None],
                                          attend, cache["latent"])
        return {"latent": pool,
                **self._recorded_decode(cache, sel, page_of, off,
                                        page_of != trash)}, \
            _dsv3.head_logits(params, x[0], cfg)


class WindowedDecodeModel(_ExpertRecords, DecodeModel):
    """Serving adapter around `models/afmoe.py`: grouped-query attention of
    two kinds, and so two kinds of K/V state. The full layers attend to
    every cached position: `k_full`, `v_full` [full layers, P+1, ps, Hkv,
    d], paged under the request's table as every other model's. The sliding
    layers attend to the last `sliding_window` positions: `k_win`, `v_win`
    [sliding layers, S R + 1, ps, Hkv, d], a RING of R = window / ps + 1
    pages a SLOT (position t in page slot R + (t // ps) mod R; the last
    page is trash). A cached token older than the window holds no bytes in
    a sliding layer; a slot's ring is its request's whole, so nothing is
    allocated, passed or moved for it: the scheduler and the page pool know
    one table a request, as for every model. The routing part lies under
    that table; the expert tallies are `_ExpertRecords'`.

    `prefill` and `decode` are drivers over `afmoe.apply_layers`. Prefill
    attends over the bucket itself (`layers.gated_causal_attention`): with
    `attn_impl` "pallas" (a TPU's default) through the flash kernel, a band
    of `afmoe.window_of(cfg, l)` on a sliding layer and the triangle on a
    full one, the 4 KV heads read in place by their 8 query heads each, for
    every bucket the kernel's blocks divide and the gate measures it
    faster at (one key a bucket and kind: docs/KERNELS.md); else, and
    elsewhere, XLA's float32 scores in row blocks
    (`afmoe.banded_causal_attention`). It writes every position to the
    full layers' pages and, to the slot's ring, the positions whose page is
    among the prompt's last R (older ones are never cached there). Decode
    writes one row to each; the full layers attend through
    `paged_attention_decode`, the sliding layers through the XLA path from
    the slot's first live position over the ring."""

    cache_kinds = {"k_full": "paged", "v_full": "paged", "k_win": "slot",
                   "v_win": "slot", **_ExpertRecords._expert_kinds}

    def __init__(self, cfg: "_afmoe.AfmoeConfig", params=None, seed: int = 0,
                 attn_impl: str | None = None):
        # the full layers' table is max_seq_len wide and the XLA path
        # gathers every entry of every slot, K and V (64 slots of 288
        # pages of 64 KiB: 2.4 GB a step, held beside the next prefill's
        # temporaries: 15.3 of 15.75 GiB); the kernel reads live pages in
        # place, in the same time (PERF.md, PR 40). Not the gate's to draw
        kernel = on_tpu() and not os.environ.get("PADDLE_TPU_DISABLE_PALLAS")
        super().__init__(cfg, params if params is not None
                         else _afmoe.init_params(cfg, seed),
                         attn_impl or ("pallas" if kernel else "xla"))
        self.window = cfg.sliding_window

    def init_cache(self, num_pages: int, page_size: int, num_slots: int):
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        tail = (page_size, cfg.num_key_value_heads, cfg.head_dim)
        full = (cfg.layers_of(_afmoe.FULL), num_pages + 1) + tail
        win = (cfg.layers_of(_afmoe.SLIDING),
               num_slots * self.ring_pages(page_size) + 1) + tail
        return {"k_full": jnp.zeros(full, dt), "v_full": jnp.zeros(full, dt),
                "k_win": jnp.zeros(win, dt), "v_win": jnp.zeros(win, dt),
                **self._expert_parts(num_pages, page_size)}

    def _pools(self, cache):
        return {_afmoe.FULL: (cache["k_full"], cache["v_full"]),
                _afmoe.SLIDING: (cache["k_win"], cache["v_win"])}

    @staticmethod
    def _parts(pools) -> dict:
        (kf, vf), (kw, vw) = pools[_afmoe.FULL], pools[_afmoe.SLIDING]
        return {"k_full": kf, "v_full": vf, "k_win": kw, "v_win": vw}

    # -- prefill -------------------------------------------------------
    def prefill(self, params, cache, tokens, true_len, page_row, slot):
        """tokens [T] int32 (padded bucket), true_len and slot scalar
        int32, page_row [M] int32 (fill = trash). Returns (cache, logits
        [V]) of the last real position."""
        cfg = self.cfg
        T = tokens.shape[0]
        ps = cache["k_full"].shape[2]
        n = T // ps
        pages = page_row[:n]
        # the ring: logical page j of the prompt lies in the slot's page
        # j mod R, if it is among the prompt's last R pages; older pages,
        # and the bucket's padding, go to the trash page
        R = self.ring_pages(ps)
        trash = cache["k_win"].shape[1] - 1
        j = jnp.arange(n, dtype=jnp.int32)
        n_prompt = (true_len + ps - 1) // ps
        kept = jnp.logical_and(j < n_prompt, j >= n_prompt - R)
        ring_pages = jnp.where(kept, slot * R + j % R, trash)
        dest = {_afmoe.FULL: pages, _afmoe.SLIDING: ring_pages}
        x = _afmoe.embed_tokens(params, tokens, cfg)[None]      # [1, T, D]
        positions = jnp.arange(T, dtype=jnp.int32)[None]
        scale = 1.0 / math.sqrt(cfg.head_dim)

        def attend(q, k, v, pools, l):
            kind, i = cfg.layer_types[l], cfg.index_in_kind(l)
            ck, cv = pools[kind]
            shape = (n, ps) + k.shape[2:]
            pools = {**pools, kind: (
                ck.at[i, dest[kind]].set(k[0].reshape(shape).astype(ck.dtype)),
                cv.at[i, dest[kind]].set(v[0].reshape(shape).astype(cv.dtype)))}
            return _layers.gated_causal_attention(
                q, k, v, scale, _afmoe.window_of(cfg, l),
                self.attn_impl == "pallas",
                xla=_afmoe.banded_causal_attention), pools

        x, pools, sel = _afmoe.apply_layers(cfg, params, x, positions, attend,
                                            self._pools(cache))
        xlast = jax.lax.dynamic_index_in_dim(x[0], true_len - 1, 0,
                                             keepdims=False)
        real = jnp.arange(T, dtype=jnp.int32) < true_len
        return {**self._parts(pools),
                **self._recorded_prefill(cache, sel, pages, real)}, \
            _afmoe.head_logits(params, xlast, cfg)

    # -- decode --------------------------------------------------------
    def decode(self, params, cache, tokens, positions, tables):
        """tokens/positions [S] int32, tables [S, M] int32 (fill = trash;
        inactive slots = all-trash rows with position 0). Row i is slot i.
        In each layer: the slot's K/V to its position's page and offset,
        under its table or in its ring (an inactive slot's to either's
        trash page), then ragged paged attention, a sliding layer's from
        position - window + 1 over the ring. Returns (cache, logits [S,
        V])."""
        cfg = self.cfg
        S = tokens.shape[0]
        ps, trash = cache["k_full"].shape[2], cache["k_full"].shape[1] - 1
        R = self.ring_pages(ps)
        # the slot batch as ONE row of S positions, each with its own
        # position and history: the layers' products are [S, D] x [D, .]
        x = _afmoe.embed_tokens(params, tokens, cfg)[None]      # [1, S, D]
        page = positions // ps
        page_of = jnp.take_along_axis(tables, page[:, None], axis=1)[:, 0]
        live = page_of != trash
        rings = jnp.arange(S * R, dtype=jnp.int32).reshape(S, R)
        at = {_afmoe.FULL: page_of,
              _afmoe.SLIDING: jnp.where(
                  live, jnp.arange(S, dtype=jnp.int32) * R + page % R,
                  cache["k_win"].shape[1] - 1)}
        off = positions % ps
        ctx = positions + 1
        first = jnp.maximum(ctx - cfg.sliding_window, 0)
        scale = 1.0 / math.sqrt(cfg.head_dim)

        def attend(q, k, v, pools, l):
            kind, i = cfg.layer_types[l], cfg.index_in_kind(l)
            ck, cv = pools[kind]
            ck = ck.at[i, at[kind], off].set(k[0].astype(ck.dtype))
            cv = cv.at[i, at[kind], off].set(v[0].astype(cv.dtype))
            if kind == _afmoe.FULL:
                a = paged_attention_decode(
                    q[0], ck, cv, tables, ctx, layer=i, scale=scale,
                    impl=self.attn_impl)
            else:
                a = paged_attention_xla(
                    q[0], ck, cv, rings, ctx, layer=i, scale=scale,
                    first=first, ring=R)
            return a.reshape(1, S, -1), {**pools, kind: (ck, cv)}

        x, pools, sel = _afmoe.apply_layers(cfg, params, x, positions[None],
                                            attend, self._pools(cache))
        return {**self._parts(pools),
                **self._recorded_decode(cache, sel, page_of, off, live)}, \
            _afmoe.head_logits(params, x[0], cfg)


class RecurrentDecodeModel(DecodeModel):
    """Serving adapter around `models/jamba.py`: Mamba layers beside a few
    multi-query attention layers. A Mamba layer's state is per SLOT and
    does not grow with the sequence: `ssm` [Mamba layers, S, N, E] float32
    (the recurrence's h) and `conv` [Mamba layers, K-1, S, E] (the
    convolution's last K-1 inputs); at the published sizes 9,318,400 bytes
    a slot, read and written whole by every decode step, in place in the
    donated cache. An attention layer's K and V are ONE head that all the
    query heads read: a row [v | k] a token in the paged part `kv`
    [attention layers, P+1, ps, 2d] under the request's table (1 KiB a
    token over two layers), attended through the latent path (`ops/
    paged_attention.py::paged_latent_attention_decode`: H query rows
    against a block of shared rows on the MXU, the query zero in the
    value's lanes). A pool with a head axis of one would pad it to a tile
    of sixteen rows.

    `prefill` and `decode` are drivers over `jamba.apply_layers`. Prefill
    runs the chunked scan from a zero state over the bucket and writes the
    slot's rows whole from the state AT `true_len` (the padding behind it
    does not advance the recurrence, and the taps are those before
    `true_len`), so a slot never reads its last tenant's. Decode advances
    every slot's rows by one token; a dead slot's stay finite (its input
    is token 0, its decay under one) and are overwritten at admission."""

    cache_kinds = {"kv": "paged", "ssm": "slot", "conv": "slot"}
    scan_chunk = SCAN_CHUNK

    def __init__(self, cfg: "_jamba.JambaConfig", params=None, seed: int = 0,
                 attn_impl: str | None = None):
        if cfg.num_key_value_heads != 1:
            raise NotImplementedError(
                f"num_key_value_heads = {cfg.num_key_value_heads}: the "
                f"paged part holds one shared [v | k] row a token")
        super().__init__(cfg, params if params is not None
                         else _jamba.init_params(cfg, seed), attn_impl)

    def init_cache(self, num_pages: int, page_size: int, num_slots: int):
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        ssm, conv = _jamba.zero_state(cfg, num_slots, dt)
        return {"kv": jnp.zeros((cfg.layers_of(_jamba.ATTN), num_pages + 1,
                                 page_size, 2 * cfg.head_dim), dt),
                "ssm": ssm, "conv": conv}

    # -- prefill -------------------------------------------------------
    def prefill(self, params, cache, tokens, true_len, page_row, slot):
        """tokens [T] int32 (padded bucket), true_len and slot scalar
        int32, page_row [M] int32 (fill = trash). Returns (cache, logits
        [V]) of the last real position."""
        cfg = self.cfg
        T = tokens.shape[0]
        ps = cache["kv"].shape[2]
        pages = page_row[:T // ps]
        x = jnp.take(params["embed"], tokens, axis=0)[None]     # [1, T, D]
        scale = 1.0 / math.sqrt(cfg.head_dim)

        def attend(q, k, v, pool, i):
            row = jnp.concatenate([v, k], axis=-1)[0, :, 0]     # [T, 2d]
            pool = pool.at[i, pages].set(
                row.reshape(T // ps, ps, -1).astype(pool.dtype))
            return _layers.dense_causal_attention(q, k, v, scale), pool

        x, ssm, conv, pool = _jamba.apply_layers(
            cfg, params, x, *_jamba.zero_state(cfg, 1, cache["conv"].dtype),
            attend, cache["kv"], lengths=jnp.reshape(true_len, (1,)))
        xlast = jax.lax.dynamic_index_in_dim(x[0], true_len - 1, 0,
                                             keepdims=False)
        put = jax.lax.dynamic_update_slice_in_dim
        return {"kv": pool, "ssm": put(cache["ssm"], ssm, slot, axis=1),
                "conv": put(cache["conv"], conv, slot, axis=2)}, \
            _jamba.head_logits(params, xlast, cfg)

    # -- decode --------------------------------------------------------
    def decode(self, params, cache, tokens, positions, tables):
        """tokens/positions [S] int32, tables [S, M] int32 (fill = trash;
        inactive slots = all-trash rows with position 0). Row i is slot i.
        Every Mamba layer advances every slot's state by its token; an
        attention layer writes the slot's row to its position's page and
        offset, then attends over the slot's cached rows. Returns (cache,
        logits [S, V])."""
        cfg = self.cfg
        S, d = tokens.shape[0], cfg.head_dim
        ps = cache["kv"].shape[2]
        x = jnp.take(params["embed"], tokens, axis=0)[:, None]  # [S, 1, D]
        page_of = jnp.take_along_axis(
            tables, (positions // ps)[:, None], axis=1)[:, 0]
        off = positions % ps
        ctx = positions + 1
        scale = 1.0 / math.sqrt(d)

        def attend(q, k, v, pool, i):
            row = jnp.concatenate([v, k], axis=-1)[:, 0, 0]     # [S, 2d]
            pool = pool.at[i, page_of, off].set(row.astype(pool.dtype))
            q = q[:, 0]                                         # [S, H, d]
            o = paged_latent_attention_decode(
                jnp.concatenate([jnp.zeros_like(q), q], axis=-1), pool,
                tables, ctx, value_width=d, scale=scale, layer=i,
                impl=self.attn_impl)
            return o.reshape(S, 1, -1), pool

        x, ssm, conv, pool = _jamba.apply_layers(
            cfg, params, x, cache["ssm"], cache["conv"], attend, cache["kv"])
        return {"kv": pool, "ssm": ssm, "conv": conv}, \
            _jamba.head_logits(params, x[:, 0], cfg)
