"""Model assembly: embeddings -> (prefix blocks, scanned unit groups) ->
norm -> logits, with train / prefill / decode entry points.

Scan-over-layer-groups: ``cfg.grouping()`` factors the block pattern into
``prefix + unit * repeats``; the prefix is unrolled and the unit is scanned
with stacked params — compile time is O(prefix + unit), not O(depth), which
is what makes the 100-layer dry-runs compile in minutes. Zamba2's
weight-shared attention block is closed over by the scan body (one param
set, per-repeat KV caches ride through the scan's xs/ys).

A paged KV group's page leaves are the exception: they ride in the scan's
CARRY, stacked as they are, with the layer index in xs, and each layer
writes its new rows into the stacked pool and reads its layer where it
lies (``attention.PAGE_LAYER``). As xs/ys, every layer would slice its
pages out and write them back whole, and the scan's output pool could not
alias its input, so a fused decode loop would copy the pool every
micro-step. Cursors (``pos_ids``/``length``) and every other cache keep
the xs/ys form.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as A
from repro.models import blocks as B
from repro.models import layers as L
from repro.models.config import ModelConfig
from repro.sharding import shard

AUX_WEIGHT_KEYS = {"moe_aux": "router_aux_weight", "moe_z": "router_z_weight"}
# aux entries that count work (summed over layers), which the serving
# steps hand back per call: the dropless MoE layer's pairs and experts hit
COUNTER_KEYS = ("moe_pairs", "moe_experts_hit")
_SCAN_AUX_KEYS = ("moe_aux", "moe_z", "moe_drop_frac")

# Block kinds safe under right-padded batched prefill: attention kinds mask
# pad keys via pos_ids == -1; mamba2 freezes its state on masked tokens.
# rwkv6 (no mask plumbing) and memory-conditioned kinds (cross/dec/enc) are
# excluded — the serving engine falls back to exact-length batching there.
PADDED_PREFILL_KINDS = {"dense", "parallel", "moe", "mla", "mla_moe",
                        "shared", "mamba2"}


def _stack_trees(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


class Model:
    """Functional model bound to a ModelConfig. All methods are pure."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.prefix, self.unit, self.repeats = cfg.grouping()
        self.prefix_len = len(self.prefix)

    @property
    def supports_padded_prefill(self) -> bool:
        """True when right-padded batched prefill is exact for this model."""
        kinds = set(self.prefix) | set(self.unit)
        return (not self.cfg.is_encdec
                and kinds <= PADDED_PREFILL_KINDS)

    @property
    def supports_paged_decode(self) -> bool:
        """True when decode KV state can live in a shared paged pool: every
        block is a full-attention kind (uniform cache width, no ring
        eviction to translate; MLA's latent pages as one head of the
        latent and one of the rope key) or carries fixed-size recurrent
        state (mamba2, which simply stays slot-addressed).
        Windowed/chunked attention keeps the contiguous ring."""
        kinds = set(self.prefix) | set(self.unit)
        return (not self.cfg.is_encdec
                and kinds <= {"dense", "parallel", "moe", "shared", "mamba2",
                              "mla", "mla_moe"}
                and self.cfg.sliding_window is None
                and self.cfg.attn_chunk is None)

    @property
    def supports_chunked_prefill(self) -> bool:
        """True when prompt processing can be split into fixed-size chunks
        interleaved with decode: every stateful block's KV must live in the
        paged pool, because chunk i reaches chunks 0..i-1 through the block
        table. Recurrent blocks (mamba2) would need carried-state chunk
        resume and keep the monolithic prefill path for now."""
        kinds = set(self.prefix) | set(self.unit)
        return (self.supports_paged_decode
                and kinds <= {"dense", "parallel", "moe", "shared", "mla",
                              "mla_moe"})

    # ------------------------------------------------------------------ init
    def init(self, key) -> Dict:
        cfg = self.cfg
        keys = jax.random.split(key, 8)
        params: Dict = {"embed": L.embedding_init(keys[0], cfg)}
        kb = jax.random.split(keys[1], max(len(self.prefix), 1))
        params["prefix"] = tuple(
            B.block_init(kb[i], cfg, kind) for i, kind in enumerate(self.prefix))
        if self.repeats:
            shared_done = False
            unit_params = []
            for r in range(self.repeats):
                kr = jax.random.fold_in(keys[2], r)
                ku = jax.random.split(kr, len(self.unit))
                entry = {}
                for i, kind in enumerate(self.unit):
                    if kind == "shared":
                        if not shared_done:
                            params["shared_block"] = B.block_init(ku[i], cfg, kind)
                            shared_done = True
                        continue
                    entry[str(i)] = B.block_init(ku[i], cfg, kind)
                unit_params.append(entry)
            params["unit"] = _stack_trees(unit_params)
        if cfg.is_encdec:
            ke = jax.random.split(keys[3], cfg.n_encoder_layers)
            params["encoder"] = _stack_trees(
                [B.block_init(k, cfg, "enc") for k in ke])
            params["enc_norm"] = L.rmsnorm_init(cfg.d_model, L.dtype_of(cfg))
        params["final_norm"] = L.rmsnorm_init(cfg.d_model, L.dtype_of(cfg))
        params["lm_head"] = L.lm_head_init(keys[4], cfg)
        if cfg.mtp:
            params["mtp"] = {
                "proj": L.dense_init(keys[5], 2 * cfg.d_model, cfg.d_model,
                                     L.dtype_of(cfg)),
                "norm": L.rmsnorm_init(cfg.d_model, L.dtype_of(cfg)),
                "block": B.block_init(keys[6], cfg, "dense"),
            }
        return params

    def param_shapes(self) -> Dict:
        return jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))

    # ----------------------------------------------------------------- cache
    def init_cache(self, batch: int, max_len: int) -> Dict:
        cfg = self.cfg
        caches: Dict = {"t": jnp.zeros((batch,), jnp.int32)}
        caches["prefix"] = tuple(
            B.block_cache_init(cfg, kind, batch, max_len, layer_idx=i)
            for i, kind in enumerate(self.prefix))
        if self.repeats:
            per_pos = {}
            for i, kind in enumerate(self.unit):
                c = B.block_cache_init(cfg, kind, batch, max_len,
                                       layer_idx=self.prefix_len + i)
                per_pos[str(i)] = jax.tree_util.tree_map(
                    lambda x: jnp.broadcast_to(x, (self.repeats,) + x.shape).copy(), c
                ) if c is not None else None
            caches["unit"] = per_pos
        return caches

    def cache_shapes(self, batch: int, max_len: int) -> Dict:
        return jax.eval_shape(lambda: self.init_cache(batch, max_len))

    # --------------------------------------------------------------- forward
    def _encode(self, params, frames, mask):
        cfg = self.cfg
        x = frames
        positions = jnp.broadcast_to(
            jnp.arange(x.shape[1], dtype=jnp.int32)[None], x.shape[:2])
        ctx = B.LayerCtx(cfg=cfg, mode="train", positions=positions, mask=mask)

        def body(h, p):
            h, _, _ = B.block_apply(p, cfg, "enc", ctx, h, None)
            return h, None

        x, _ = jax.lax.scan(body, x, params["encoder"])
        return L.rmsnorm(params["enc_norm"], x, cfg.norm_eps)

    def _backbone(self, params, x, ctx: B.LayerCtx, caches, remat: bool):
        cfg = self.cfg
        aux_tot: Dict[str, jax.Array] = {}

        def add_aux(aux):
            for k, v in aux.items():
                aux_tot[k] = aux_tot.get(k, 0.0) + v

        new_prefix = []
        for i, kind in enumerate(self.prefix):
            c = caches["prefix"][i] if caches is not None else None
            ctx_i = dataclasses.replace(ctx, layer_idx=i)
            x, c, aux = B.block_apply(params["prefix"][i], cfg, kind, ctx_i, x, c)
            add_aux(aux)
            new_prefix.append(c)

        if self.repeats:
            unit = self.unit
            shared_p = params.get("shared_block")
            needs_emb = "shared" in unit

            def unit_body(carry, xs):
                h, emb, pages = carry
                # pin the residual-stream sharding: conflicting uses inside
                # the body (head-sharded attention vs all-axes-sharded MoE
                # shard_map) otherwise degrade the scan carry to replicated
                # (EXPERIMENTS.md SSPerf H1 iter 3)
                h = shard(h, "batch", "seq", "embed")
                p_entry, c_entry, layer = xs
                pages = dict(pages)
                aux_list = []
                for i, kind in enumerate(unit):
                    ctx_i = dataclasses.replace(
                        ctx, layer_idx=self.prefix_len + i, emb_orig=emb)
                    p_i = shared_p if kind == "shared" else p_entry[str(i)]
                    c_i = None if c_entry is None else c_entry[str(i)]
                    pg_i = pages.get(str(i))
                    if pg_i is not None:    # the stacked pool at this layer
                        c_i = {**c_i, **pg_i, A.PAGE_LAYER: layer}
                    h, c_i, aux = B.block_apply(p_i, cfg, kind, ctx_i, h, c_i)
                    aux_list.append(aux)
                    if pg_i is not None:
                        pages[str(i)] = {n: c_i[n] for n in pg_i}
                        c_i = {n: v for n, v in c_i.items()
                               if n not in pg_i and n != A.PAGE_LAYER}
                    if c_entry is not None:
                        c_entry = dict(c_entry)
                        c_entry[str(i)] = c_i
                merged: Dict = {}
                for a in aux_list:
                    for k, v in a.items():
                        merged[k] = merged.get(k, 0.0) + v
                pad_aux = {k: jnp.asarray(merged.get(k, 0.0), jnp.float32)
                           for k in _SCAN_AUX_KEYS}
                pad_aux.update({k: v for k, v in merged.items()
                                if k in COUNTER_KEYS})
                h = shard(h, "batch", "seq", "embed")
                return (h, emb, pages), (c_entry, pad_aux)

            body = unit_body
            if remat:
                body = jax.checkpoint(unit_body, prevent_cse=False)
            unit_caches = caches["unit"] if caches is not None else None
            if unit_caches is None:
                unit_caches = {str(i): None for i in range(len(unit))}
                xs = (params["unit"], None)
                # scan needs concrete xs; replace None caches with empty arrays
                xs = (params["unit"],
                      jnp.zeros((self.repeats, 0), jnp.float32))

                def body_nc(carry, p_entry_and_pad):
                    p_entry, _ = p_entry_and_pad
                    return body(carry, (p_entry, None, None))

                (x, _, _), (_, aux_scan) = jax.lax.scan(
                    body_nc, (x, ctx.emb_orig, {}), xs)
                new_unit = None
            else:
                # page leaves ride in the carry (module docstring)
                pages = {k: {n: g[n] for n in A.page_leaf_names(g)}
                         for k, g in unit_caches.items()
                         if A.page_leaf_names(g)}
                rest = {k: ({n: v for n, v in g.items() if n not in pages[k]}
                            if k in pages else g)
                        for k, g in unit_caches.items()}
                (x, _, pages), (new_unit, aux_scan) = jax.lax.scan(
                    body, (x, ctx.emb_orig, pages),
                    (params["unit"], rest,
                     jnp.arange(self.repeats, dtype=jnp.int32)))
                new_unit = {k: ({**g, **pages[k]} if k in pages else g)
                            for k, g in new_unit.items()}
            for k, v in aux_scan.items():
                aux_tot[k] = aux_tot.get(k, 0.0) + jnp.sum(v)
        else:
            new_unit = caches.get("unit") if caches is not None else None

        new_caches = None
        if caches is not None:
            new_caches = dict(caches)
            new_caches["prefix"] = tuple(new_prefix)
            if self.repeats:
                new_caches["unit"] = new_unit
        return x, new_caches, aux_tot

    def forward(self, params, tokens, extras: Optional[Dict] = None,
                mode: str = "train", caches: Optional[Dict] = None,
                remat: bool = False):
        """Returns (logits, new_caches, aux)."""
        cfg = self.cfg
        extras = extras or {}
        Bsz, S = tokens.shape
        mask = extras.get("mask")
        if mode == "decode":
            positions = caches["t"][:, None]
        elif mode == "chunk":
            # prompt chunk: positions continue from the slot's token count;
            # pad queries (partial last chunk) get -1 like padded prefill
            positions = caches["t"][:, None] + jnp.arange(S, dtype=jnp.int32)
            if mask is not None:
                positions = jnp.where(mask > 0, positions, -1)
        else:
            positions = jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32)[None], (Bsz, S))
        if mode == "prefill" and mask is not None:
            # right-padded batched prefill: pad slots get position -1, so
            # their cache entries are masked (pos_ids == -1 = empty) and no
            # real token ever attends to them
            positions = jnp.where(mask > 0, positions, -1)

        x = L.embed(params["embed"], cfg, tokens)
        if "image_embeds" in extras and cfg.n_image_tokens == 0:
            # early fusion (llama4): image embeddings replace token slots
            img = extras["image_embeds"].astype(x.dtype)
            pos = extras["image_positions"]
            bidx = jnp.arange(Bsz)[:, None]
            x = x.at[bidx, pos].set(img)

        memory = None
        if cfg.is_encdec and mode != "decode":
            memory = self._encode(params, extras["frames"].astype(x.dtype),
                                  extras.get("frames_mask"))
        elif cfg.n_image_tokens and "image_embeds" in extras:
            memory = extras["image_embeds"].astype(x.dtype)

        emb_orig = x if any(k == "shared" for k in cfg.block_pattern) else None
        # paged serving: the shared block table rides the cache tree once
        # (caches["paged"]) and reaches every attention layer through ctx
        page_tbl = None
        if mode in ("decode", "chunk") and caches is not None \
                and "paged" in caches:
            page_tbl = caches["paged"]["tbl"]
        ctx = B.LayerCtx(cfg=cfg, mode=mode, positions=positions, mask=mask,
                         memory=memory, emb_orig=emb_orig, batch=Bsz,
                         max_len=0, page_tbl=page_tbl)
        x, new_caches, aux = self._backbone(params, x, ctx, caches, remat)
        if mode == "chunk":
            # only the last REAL token's logits matter (next-chunk callers
            # discard them; the final chunk samples the first decode token);
            # no mask means the whole chunk is real
            nv = (mask.sum(axis=1).astype(jnp.int32) if mask is not None
                  else jnp.full((Bsz,), S, jnp.int32))
            idx = jnp.maximum(nv - 1, 0)
            x = jnp.take_along_axis(x, idx[:, None, None], axis=1)  # (B,1,d)
        with jax.named_scope("lm_head"):
            x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
            logits = L.logits(params["lm_head"], params["embed"], cfg, x)
        if mode == "decode" and new_caches is not None:
            new_caches["t"] = new_caches["t"] + 1
        elif mode == "chunk" and new_caches is not None:
            nv = (mask.sum(axis=1).astype(jnp.int32) if mask is not None
                  else jnp.full((Bsz,), S, jnp.int32))
            new_caches["t"] = new_caches["t"] + nv
        elif mode == "prefill" and new_caches is not None:
            lengths = (mask.sum(axis=1).astype(jnp.int32) if mask is not None
                       else jnp.full((Bsz,), S, jnp.int32))
            new_caches["t"] = lengths
        if cfg.mtp and mode == "train":
            aux = dict(aux)
            aux["_hidden"] = x        # reused by the MTP head in train_loss
        return logits, new_caches, aux

    # ---------------------------------------------------------------- train
    def train_loss(self, params, batch: Dict, remat: bool = True):
        """batch: tokens (B,S), labels (B,S) (-100 = ignore), extras..."""
        cfg = self.cfg
        tokens, labels = batch["tokens"], batch["labels"]
        extras = {k: v for k, v in batch.items()
                  if k not in ("tokens", "labels")}
        logits, _, aux = self.forward(params, tokens, extras, mode="train",
                                      remat=remat)
        loss, n_tok = _masked_ce(logits, labels, cfg.vocab)
        metrics = {"ce": loss, "tokens": n_tok}
        total = loss
        if cfg.moe is not None:
            total = total + cfg.moe.router_aux_weight * aux.get("moe_aux", 0.0)
            total = total + cfg.moe.router_z_weight * aux.get("moe_z", 0.0)
            metrics["moe_aux"] = aux.get("moe_aux", 0.0)
            metrics["moe_drop_frac"] = aux.get("moe_drop_frac", 0.0)
        if cfg.mtp and "_hidden" in aux:
            h = aux["_hidden"]
            emb_next = L.embed(params["embed"], cfg,
                               jnp.roll(tokens, -1, axis=1))
            hm = jnp.einsum(
                "btd,dk->btk",
                jnp.concatenate([L.rmsnorm(params["mtp"]["norm"], h,
                                           cfg.norm_eps), emb_next], -1),
                params["mtp"]["proj"])
            pos = jnp.broadcast_to(
                jnp.arange(tokens.shape[1], dtype=jnp.int32)[None],
                tokens.shape)
            ctx = B.LayerCtx(cfg=cfg, mode="train", positions=pos)
            hm, _, _ = B.block_apply(params["mtp"]["block"], cfg, "dense",
                                     ctx, hm, None)
            mtp_logits = L.logits(params["lm_head"], params["embed"], cfg, hm)
            mtp_labels = jnp.roll(labels, -1, axis=1).at[:, -1].set(-100)
            mtp_loss, _ = _masked_ce(mtp_logits, mtp_labels, cfg.vocab)
            total = total + cfg.mtp_weight * mtp_loss
            metrics["mtp_ce"] = mtp_loss
        metrics["loss"] = total
        return total, metrics

    # ---------------------------------------------------------------- serve
    def prefill(self, params, tokens, extras: Optional[Dict] = None,
                max_len: Optional[int] = None, caches: Optional[Dict] = None):
        """Process prompts; returns (last-token logits (B, vocab), caches)."""
        Bsz, S = tokens.shape
        if caches is None:
            caches = self.init_cache(Bsz, max_len or S)
        logits, caches, _ = self.forward(params, tokens, extras,
                                         mode="prefill", caches=caches)
        idx = jnp.maximum(caches["t"] - 1, 0)
        last = jnp.take_along_axis(
            logits, idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        return last, caches

    def prefill_chunk(self, params, caches, tokens, mask,
                      counters: bool = False):
        """Process one fixed-size prompt chunk against a paged slot view
        (``serving.paged.gather_slot_view``): the chunk's KV is appended to
        the slots' pages and its queries attend over each slot's whole
        logical history (prior chunks + itself, causally). tokens/mask:
        (n, C); positions continue from ``caches['t']``. Returns
        (last-valid-token logits (n, vocab), caches), and with
        ``counters`` the chunk's ``COUNTER_KEYS`` aux (empty for a model
        without them)."""
        logits, caches, aux = self.forward(params, tokens, {"mask": mask},
                                           mode="chunk", caches=caches)
        if counters:
            return logits[:, 0], caches, _counters(aux)
        return logits[:, 0], caches

    def decode_step(self, params, caches, tokens, counters: bool = False):
        """tokens: (B, 1) -> (logits (B, vocab), caches), and with
        ``counters`` the step's ``COUNTER_KEYS`` aux."""
        logits, caches, aux = self.forward(params, tokens, None,
                                           mode="decode", caches=caches)
        if counters:
            return logits[:, 0], caches, _counters(aux)
        return logits[:, 0], caches


def _counters(aux: Dict) -> Dict:
    return {k: jnp.asarray(aux[k], jnp.int32) for k in COUNTER_KEYS
            if k in aux}


def _masked_ce(logits: jax.Array, labels: jax.Array, vocab: int):
    if logits.shape[-1] > vocab:        # exclude padded vocab classes
        pad = logits.shape[-1] - vocab
        neg = jnp.full((pad,), -1e9, logits.dtype)
        logits = jnp.concatenate(
            [logits[..., :vocab],
             jnp.broadcast_to(neg, logits.shape[:-1] + (pad,))], axis=-1)
    mask = (labels >= 0)
    safe = jnp.where(mask, labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    nll = (lse - gold) * mask
    n = jnp.maximum(mask.sum(), 1)
    return nll.sum() / n, n
