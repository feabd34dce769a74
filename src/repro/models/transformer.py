"""TransformerLM: one scan-based decoder implementation covering the
dense / moe / vlm / audio / hybrid families (RWKV6 lives in rwkv.py).

Structure
---------
- ``init`` builds a params pytree whose repeated-block leaves are stacked
  along a leading axis of length ``L_super = num_layers // moe_interleave``;
  the training/prefill forward is one ``lax.scan`` over that stack (HLO size
  independent of depth — required to keep 80 dry-run compiles tractable on
  one CPU core).
- The decode path is *unrolled* per layer so each layer's packed quantized
  weights specialize to their own ReLeQ bitwidth (DESIGN.md §3): a scan
  cannot stack buffers whose plane count differs per layer.
- Every weight matmul goes through ``apply_linear`` which accepts either a
  raw array (training / fp serving) or a packed ``{planes, scale, bits}``
  dict (quantized serving via kernels.ops.qmm).

A "sub" is one attention+FFN residual block.  ``moe_interleave=2`` (llama4)
makes the scanned superblock = [dense sub, moe sub]; ``family="hybrid"``
(hymba) gives each sub parallel attention+SSM branches.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import fused_decode
from repro.kernels import ops as kops
from repro.quant.pack import (QDQ, Packed, kv_dequantize, kv_pack_int4,
                              kv_qdq, kv_quantize, kv_unpack_int4)
from repro.quant.wrpn import fake_quant as wrpn_fake_quant
from repro.models import mamba as mamba_mod
from repro.models.common import (
    apply_linear,
    apply_mrope,
    apply_rope,
    batch_axes,
    blocked_attention,
    chunk_attention,
    constrain,
    decode_attention,
    dense_init,
    embed_init,
    model_axis,
    readout_axes,
    rms_norm,
    seq_axis,
    swiglu,
)
from repro.models.model import QuantGroup
from repro.models.moe import init_moe, moe_ffn


class TransformerLM:
    def __init__(self, cfg: ModelConfig):
        if cfg.family == "ssm":
            raise ValueError("use RWKV6LM for family='ssm'")
        self.cfg = cfg
        self.n_sub = cfg.moe_interleave if cfg.num_experts else 1
        if cfg.num_layers % self.n_sub:
            raise ValueError("num_layers must divide moe_interleave")
        self.L_super = cfg.num_layers // self.n_sub

    # ------------------------------------------------------------------ init
    def _init_attn(self, key, dtype):
        cfg = self.cfg
        D, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.hd
        ks = jax.random.split(key, 4)
        return {
            "wq": dense_init(ks[0], D, H * hd, dtype),
            "wk": dense_init(ks[1], D, KV * hd, dtype),
            "wv": dense_init(ks[2], D, KV * hd, dtype),
            "wo": dense_init(ks[3], H * hd, D, dtype, scale=(H * hd) ** -0.5),
        }

    def _init_mlp(self, key, dtype):
        cfg = self.cfg
        ks = jax.random.split(key, 3)
        return {
            "wg": dense_init(ks[0], cfg.d_model, cfg.d_ff, dtype),
            "wu": dense_init(ks[1], cfg.d_model, cfg.d_ff, dtype),
            "wd": dense_init(ks[2], cfg.d_ff, cfg.d_model, dtype, scale=cfg.d_ff ** -0.5),
        }

    def _init_sub(self, key, sub: int, dtype):
        cfg = self.cfg
        ks = jax.random.split(key, 5)
        p = {
            "ln1": jnp.ones((cfg.d_model,), jnp.float32),
            "ln2": jnp.ones((cfg.d_model,), jnp.float32),
            "attn": self._init_attn(ks[0], dtype),
        }
        is_moe_sub = cfg.num_experts and sub == self.n_sub - 1
        if is_moe_sub:
            p["moe"] = init_moe(ks[1], cfg.num_experts, cfg.d_model, cfg.d_ff, dtype)
            if cfg.shared_expert:
                p["shared"] = self._init_mlp(ks[2], dtype)
        else:
            p["mlp"] = self._init_mlp(ks[1], dtype)
        if cfg.family == "hybrid":
            p["ssm"] = mamba_mod.init_mamba(
                ks[3], cfg.d_model, cfg.ssm_expand * cfg.d_model, cfg.ssm_state,
                cfg.ssm_conv, dtype)
            p["mix"] = jnp.asarray(0.5, jnp.float32)
        return p

    def init(self, rng) -> dict:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        k_emb, k_head, k_blocks = jax.random.split(rng, 3)
        subs = []
        for s in range(self.n_sub):
            keys = jax.random.split(jax.random.fold_in(k_blocks, s), self.L_super)
            subs.append(jax.vmap(lambda k: self._init_sub(k, s, dtype))(keys))
        params = {
            "embed": embed_init(k_emb, cfg.vocab_size, cfg.d_model, dtype),
            "blocks": subs,
            "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(k_head, cfg.d_model, cfg.vocab_size, dtype)
        return params

    # ------------------------------------------------------------- sublayers
    def _fused_decode_attn(self, h, p, cache, layer):
        """Fused quantized decode: bit-serial QKV + RoPE + KV-quantize +
        paged attention in one kernel (kernels.ops.fused_qkv_paged_decode),
        then the new token's codes/scales scattered into the pool.  The
        scatter-after-attend is numerically write-then-attend: the kernel
        folds the new token in from its own (quantized) computation."""
        cfg = self.cfg
        kc, vc, length = cache["k"][layer], cache["v"][layer], cache["length"]
        ksc, vsc = cache["k_scale"][layer], cache["v_scale"][layer]
        bt = cache["block_tables"]                      # (B, nb)
        bs = kc.shape[1]
        Tc = bt.shape[1] * bs
        out, k_codes, v_codes, k_sc, v_sc = kops.fused_qkv_paged_decode(
            h[:, 0], p["attn"]["wq"], p["attn"]["wk"], p["attn"]["wv"],
            kc, vc, ksc, vsc, bt, length, cache["kv_qmax"][layer],
            rope_theta=cfg.rope_theta, num_heads=cfg.num_heads,
            num_kv_heads=cfg.num_kv_heads)
        slot = jnp.minimum(length, Tc - 1)
        phys = jnp.take_along_axis(bt, (slot // bs)[:, None], axis=1)[:, 0]
        sub = slot % bs
        cache["k"] = cache["k"].at[layer, phys, sub].set(k_codes)
        cache["v"] = cache["v"].at[layer, phys, sub].set(v_codes)
        cache["k_scale"] = cache["k_scale"].at[layer, phys, sub].set(k_sc)
        cache["v_scale"] = cache["v_scale"].at[layer, phys, sub].set(v_sc)
        return out

    def _attn(self, x, p, positions, *, window, cache=None, layer=None):
        """Residual attention sublayer; cache != None → single-token decode."""
        cfg = self.cfg
        B, S, D = x.shape
        H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        wq, wk, wv = (p["attn"][m] for m in ("wq", "wk", "wv"))
        if (cache is not None and S == 1 and "k_scale" in cache
                and window is None and cfg.rope == "rope"
                and all(isinstance(w, Packed) for w in (wq, wk, wv))
                and fused_decode.fits_vmem(wq, wk, wv)):
            out = self._fused_decode_attn(h, p, cache, layer)
            out = out.reshape(B, S, H * hd)
            out = apply_linear(out, p["attn"]["wo"])
            return x + constrain(out, batch_axes(), seq_axis(), None)
        q = apply_linear(h, p["attn"]["wq"]).reshape(B, S, H, hd)
        k = apply_linear(h, p["attn"]["wk"]).reshape(B, S, KV, hd)
        v = apply_linear(h, p["attn"]["wv"]).reshape(B, S, KV, hd)
        if cfg.rope == "rope":
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        elif cfg.rope == "mrope":
            pos3 = positions if positions.ndim == 3 else jnp.broadcast_to(positions, (3,) + positions.shape[-2:])
            q = apply_mrope(q, pos3, cfg.rope_theta, cfg.mrope_sections)
            k = apply_mrope(k, pos3, cfg.rope_theta, cfg.mrope_sections)
        q = constrain(q, batch_axes(), None, model_axis(), None)
        k = constrain(k, batch_axes(), None, model_axis(), None)  # dropped if KV % axis
        v = constrain(v, batch_axes(), None, model_axis(), None)
        if cache is None:
            out = blocked_attention(
                q, k, v, causal=True, window=window,
                q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk)
        elif "block_tables" in cache:
            # paged pool: write the new kv into the owning block, attend by
            # block table (kernels.ops.paged_attention — Pallas on TPU,
            # gather + the identical decode_attention math on CPU)
            kc, vc, length = cache["k"][layer], cache["v"][layer], cache["length"]
            bt = cache["block_tables"]                     # (B, nb)
            bs = kc.shape[1]
            Tc = bt.shape[1] * bs                          # tokens per sequence
            slot = (length % Tc) if window is not None else jnp.minimum(length, Tc - 1)
            phys = jnp.take_along_axis(bt, (slot // bs)[:, None], axis=1)[:, 0]
            sub = slot % bs
            eff_len = jnp.minimum(length + 1, Tc)
            if "k_scale" in cache:
                # quantized blocks (unfused Pallas pair: windowed attention,
                # unpacked weights, or packed q/k/v planes above the fused
                # kernel's VMEM budget): quantize the new token, scatter codes
                # + per-(token, head) scales, attend with in-place dequant
                qmax = cache["kv_qmax"][layer]
                k_codes, k_sc = kv_quantize(k[:, 0], qmax)
                v_codes, v_sc = kv_quantize(v[:, 0], qmax)
                if kc.dtype == jnp.uint8:  # nibble-packed uniform int4
                    k_codes, v_codes = kv_pack_int4(k_codes), kv_pack_int4(v_codes)
                kc = kc.at[phys, sub].set(k_codes)
                vc = vc.at[phys, sub].set(v_codes)
                ksc = cache["k_scale"][layer].at[phys, sub].set(k_sc)
                vsc = cache["v_scale"][layer].at[phys, sub].set(v_sc)
                out = kops.paged_attention(q, kc, vc, bt, eff_len, ksc, vsc)
                cache["k_scale"] = cache["k_scale"].at[layer].set(ksc)
                cache["v_scale"] = cache["v_scale"].at[layer].set(vsc)
            else:
                if "kv_qmax" in cache:
                    # fp-KV oracle: store the quantize-dequantize value —
                    # exactly what the quantized read path reconstructs —
                    # in fp32 blocks (the token-parity gate)
                    qmax = cache["kv_qmax"][layer]
                    k_w = kv_qdq(k[:, 0], qmax).astype(kc.dtype)
                    v_w = kv_qdq(v[:, 0], qmax).astype(vc.dtype)
                else:
                    k_w, v_w = k[:, 0], v[:, 0]
                kc = kc.at[phys, sub].set(k_w)
                vc = vc.at[phys, sub].set(v_w)
                out = kops.paged_attention(q, kc, vc, bt, eff_len)
            cache["k"] = cache["k"].at[layer].set(kc)
            cache["v"] = cache["v"].at[layer].set(vc)
        else:
            # write new kv into this layer's cache slot, attend over the cache
            kc, vc, length = cache["k"][layer], cache["v"][layer], cache["length"]
            Tc = kc.shape[1]
            slot = (length % Tc) if window is not None else jnp.minimum(length, Tc - 1)
            kc = kc.at[jnp.arange(B), slot].set(k[:, 0])
            vc = vc.at[jnp.arange(B), slot].set(v[:, 0])
            eff_len = jnp.minimum(length + 1, Tc)
            out = decode_attention(q, kc, vc, eff_len)
            cache["k"] = cache["k"].at[layer].set(kc)
            cache["v"] = cache["v"].at[layer].set(vc)
        out = out.reshape(B, S, H * hd)
        out = apply_linear(out, p["attn"]["wo"])
        # tp_sp: the residual returns to a sequence-sharded layout here
        return x + constrain(out, batch_axes(), seq_axis(), None)

    def _ffn(self, x, p, *, exact: bool = False):
        cfg = self.cfg
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        aux = jnp.asarray(0.0, jnp.float32)
        if "moe" in p:
            y, aux = moe_ffn(h, p["moe"], k=cfg.experts_per_token,
                             capacity_factor=cfg.capacity_factor, no_drop=exact)
            if "shared" in p:
                y = y + self._dense_mlp(h, p["shared"])
        else:
            y = self._dense_mlp(h, p["mlp"])
        return x + y, aux

    def _dense_mlp(self, h, p):
        cfg = self.cfg
        g = apply_linear(h, p["wg"])
        if cfg.act == "swiglu":
            u = apply_linear(h, p["wu"])
            z = swiglu(g, u)
        else:
            z = jax.nn.gelu(g.astype(jnp.float32)).astype(h.dtype)
        z = constrain(z, batch_axes(), None, model_axis())
        return apply_linear(z, p["wd"])

    def _ssm_branch(self, x, p, cache=None, layer=None):
        h = rms_norm(x, p["ln1"], self.cfg.norm_eps)
        if cache is None:
            y, _ = mamba_mod.mamba_forward(h, p["ssm"], chunk=self.cfg.chunk_size)
            return y
        state = {"h": cache["ssm_h"][layer], "conv": cache["ssm_conv"][layer]}
        y, state = mamba_mod.mamba_step(h, p["ssm"], state)
        cache["ssm_h"] = cache["ssm_h"].at[layer].set(state["h"])
        cache["ssm_conv"] = cache["ssm_conv"].at[layer].set(state["conv"])
        return y

    def _sub_forward(self, x, p, positions, sub: int, *, cache=None, layer=None):
        cfg = self.cfg
        window = cfg.sliding_window
        if cfg.family == "hybrid":
            # parallel attention + SSM heads (Hymba): shared ln1, mixed output
            a = self._attn(x, p, positions, window=window, cache=cache, layer=layer) - x
            s = self._ssm_branch(x, p, cache=cache, layer=layer)
            mix = jax.nn.sigmoid(p["mix"]).astype(x.dtype)
            x = x + mix * a + (1.0 - mix) * s
        else:
            x = self._attn(x, p, positions, window=window, cache=cache, layer=layer)
        x, aux = self._ffn(x, p, exact=cache is not None)
        return x, aux

    # ------------------------------------------------------------- forwards
    def _embed_in(self, params, tokens, embeds):
        if embeds is not None:
            h = embeds.astype(jnp.dtype(self.cfg.dtype))
        else:
            emb = params["embed"]
            if isinstance(emb, QDQ):  # serving embed: quantize at lookup
                with jax.named_scope("embed_quant"):
                    emb = wrpn_fake_quant(emb.w, emb.bits, axis=0)
            h = jnp.take(emb, tokens, axis=0)
        return constrain(h, batch_axes(), None, None)

    def _positions_default(self, B, S, offset=0):
        pos = jnp.arange(S, dtype=jnp.int32)[None, :] + offset
        return jnp.broadcast_to(pos, (B, S))

    def _abs_sin(self, positions, D):
        half = D // 2
        freq = jnp.exp(-math.log(10_000.0) * jnp.arange(half, dtype=jnp.float32) / half)
        ang = positions[..., None].astype(jnp.float32) * freq
        return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)

    def forward(self, params, tokens=None, embeds=None, positions=None,
                remat: str = "none", return_hidden: bool = False):
        """Teacher-forced forward -> (logits_f32 | final hidden, aux_loss)."""
        cfg = self.cfg
        h = self._embed_in(params, tokens, embeds)
        B, S, D = h.shape
        if positions is None:
            positions = self._positions_default(B, S)
            if cfg.rope == "mrope":
                positions = jnp.broadcast_to(positions[None], (3, B, S))
        if cfg.rope == "abs_sin":
            p2 = positions if positions.ndim == 2 else positions[0]
            h = h + self._abs_sin(p2, D).astype(h.dtype)

        def superblock(h, stacked):
            aux = jnp.asarray(0.0, jnp.float32)
            for s in range(self.n_sub):
                h, a = self._sub_forward(h, stacked[s], positions, s)
                aux = aux + a
            return h, aux

        if remat == "full":
            superblock = jax.checkpoint(superblock)
        elif remat == "dots":
            superblock = jax.checkpoint(
                superblock,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

        def body(carry, stacked):
            h = carry
            h, aux = superblock(h, stacked)
            h = constrain(h, batch_axes(), seq_axis(), None)  # SP carry layout
            return h, aux

        h, auxs = jax.lax.scan(body, h, params["blocks"])
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        if return_hidden:
            return h, jnp.sum(auxs)
        logits = self._readout(params, h)
        return logits, jnp.sum(auxs)

    def _readout(self, params, h):
        w = params.get("lm_head")
        if w is None:
            emb = params["embed"]
            if isinstance(emb, QDQ):
                with jax.named_scope("embed_quant"):
                    emb = wrpn_fake_quant(emb.w, emb.bits, axis=0)
            w = emb.T
        h = constrain(h, readout_axes(), None, None)  # tokens off the model axis
        with jax.named_scope("lm_head"):
            logits = apply_linear(h, w).astype(jnp.float32)
        return constrain(logits, readout_axes(), None, "model")

    def loss(self, params, batch, remat: str = "none"):
        """Mean next-token CE (+ MoE aux), sequence-chunked readout.

        The f32 (tokens × vocab) logits never materialize whole — computed
        in rematerialized sequence chunks (3.3 GB/chip at the llama4 train
        shape otherwise; EXPERIMENTS.md §Perf)."""
        from repro.models.common import chunked_ce

        h, aux = self.forward(
            params, tokens=batch.get("tokens"), embeds=batch.get("embeds"),
            positions=batch.get("positions"), remat=remat, return_hidden=True)
        nll, z2 = chunked_ce(lambda hc: self._readout(params, hc),
                             h, batch["labels"])
        return nll + 1e-4 * z2 + 1e-2 * aux, {"nll": nll, "aux": aux}

    # --------------------------------------------------------------- decode
    def cache_len(self, max_len: int) -> int:
        w = self.cfg.sliding_window
        return min(max_len, w) if w else max_len

    def init_cache(self, batch: int, max_len: int, dtype=None):
        cfg = self.cfg
        dtype = dtype or jnp.dtype(cfg.dtype)
        L, KV, hd = cfg.num_layers, cfg.num_kv_heads, cfg.hd
        T = self.cache_len(max_len)
        cache = {
            "k": jnp.zeros((L, batch, T, KV, hd), dtype),
            "v": jnp.zeros((L, batch, T, KV, hd), dtype),
            "length": jnp.zeros((batch,), jnp.int32),
        }
        if cfg.family == "hybrid":
            Di, N = cfg.ssm_expand * cfg.d_model, cfg.ssm_state
            cache["ssm_h"] = jnp.zeros((L, batch, Di, N), jnp.float32)
            cache["ssm_conv"] = jnp.zeros((L, batch, cfg.ssm_conv - 1, Di), dtype)
        return cache

    def _layer_slice(self, params, l: int):
        """Per-layer param view: stacked pytree or pre-unrolled serving list."""
        sub = l % self.n_sub
        idx = l // self.n_sub
        stacked = params["blocks"][sub]
        if isinstance(stacked, list):  # serving layout: already per-layer list
            return stacked[idx]
        return jax.tree.map(lambda a: a[idx], stacked)

    def decode_step(self, params, cache, tokens, positions=None):
        """One token for every sequence.  tokens: (B, 1) int32.

        Unrolled over layers (each layer's quantized weights keep their own
        bitwidth).  Returns (logits (B,1,V) f32, new cache).
        """
        cfg = self.cfg
        cache = dict(cache)
        h = self._embed_in(params, tokens, None)
        B = h.shape[0]
        if positions is None:
            positions = cache["length"][:, None]
            if cfg.rope == "mrope":
                positions = jnp.broadcast_to(positions[None], (3, B, 1))
        if cfg.rope == "abs_sin":
            p2 = positions if positions.ndim == 2 else positions[0]
            h = h + self._abs_sin(p2, cfg.d_model).astype(h.dtype)
        for l in range(cfg.num_layers):
            p = self._layer_slice(params, l)
            h, _ = self._sub_forward(h, p, positions, l % self.n_sub,
                                     cache=cache, layer=l)
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        logits = self._readout(params, h)
        cache["length"] = cache["length"] + 1
        return logits, cache

    def prefill(self, params, tokens=None, embeds=None, max_len: int | None = None):
        """Forward over a prompt, building the KV cache sized for
        ``max_len`` total tokens (prompt + decode budget).  Returns
        (last-token logits, cache).  Unrolled per layer so it also accepts
        serving-layout (packed-quantized) params."""
        cfg = self.cfg
        h = self._embed_in(params, tokens, embeds)
        B, S, _ = h.shape
        positions = self._positions_default(B, S)
        pos_in = jnp.broadcast_to(positions[None], (3, B, S)) if cfg.rope == "mrope" else positions
        if cfg.rope == "abs_sin":
            h = h + self._abs_sin(positions, cfg.d_model).astype(h.dtype)
        cache = self.init_cache(B, max_len=max(S, max_len or 0, 1))
        Tc = cache["k"].shape[2]

        kv_list, ssm_list = [], []

        def run_sub(h, p, sub, layer):
            # capture this layer's K/V (and ssm state) for the cache
            hn = rms_norm(h, p["ln1"], cfg.norm_eps)
            k = apply_linear(hn, p["attn"]["wk"]).reshape(B, S, cfg.num_kv_heads, cfg.hd)
            v = apply_linear(hn, p["attn"]["wv"]).reshape(B, S, cfg.num_kv_heads, cfg.hd)
            if cfg.rope == "rope":
                k = apply_rope(k, positions, cfg.rope_theta)
            elif cfg.rope == "mrope":
                k = apply_mrope(k, pos_in, cfg.rope_theta, cfg.mrope_sections)
            kv_list.append((k[:, -Tc:], v[:, -Tc:]))
            if cfg.family == "hybrid":
                _, st = mamba_mod.mamba_forward(hn, p["ssm"], chunk=cfg.chunk_size,
                                                return_state=True)
                ssm_list.append(st)
            hn2, _ = self._sub_forward(h, p, pos_in, sub)
            return hn2

        for l in range(cfg.num_layers):
            p = self._layer_slice(params, l)
            h = run_sub(h, p, l % self.n_sub, l)

        ks = jnp.stack([kv[0] for kv in kv_list]).astype(cache["k"].dtype)
        vs = jnp.stack([kv[1] for kv in kv_list]).astype(cache["v"].dtype)
        pad = Tc - ks.shape[2]
        if pad > 0:
            ks = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
            vs = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        if cfg.sliding_window is not None:
            # ring layout: token position p lives at slot p % Tc.  The slice
            # holds the last Tmin=min(S,Tc) tokens, so element i is position
            # S-Tmin+i -> roll by (S-Tmin) % Tc.
            shift = (S - min(S, Tc)) % Tc
            ks = jnp.roll(ks, shift, axis=2)
            vs = jnp.roll(vs, shift, axis=2)
        cache["k"], cache["v"] = ks, vs
        cache["length"] = jnp.full((B,), S, jnp.int32)
        if cfg.family == "hybrid" and ssm_list:
            cache["ssm_h"] = jnp.stack([s["h"] for s in ssm_list])
            cache["ssm_conv"] = jnp.stack([s["conv"] for s in ssm_list])
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        logits = self._readout(params, h[:, -1:])
        return logits, cache

    # ----------------------------------------------------- chunked prefill
    def _chunk_attn(self, x, p, positions, pos_in, cache, layer, rows,
                    starts, valids):
        """Chunk attention sublayer against the paged pool, batched over
        pool ``rows``: each lane's queries attend [that row's cached
        pages ; the chunk itself], then the chunk's kv is scattered into
        the owning blocks (padding lanes dropped)."""
        cfg = self.cfg
        window = cfg.sliding_window
        B, C, D = x.shape
        H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        q = apply_linear(h, p["attn"]["wq"]).reshape(B, C, H, hd)
        k = apply_linear(h, p["attn"]["wk"]).reshape(B, C, KV, hd)
        v = apply_linear(h, p["attn"]["wv"]).reshape(B, C, KV, hd)
        if cfg.rope == "rope":
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        elif cfg.rope == "mrope":
            q = apply_mrope(q, pos_in, cfg.rope_theta, cfg.mrope_sections)
            k = apply_mrope(k, pos_in, cfg.rope_theta, cfg.mrope_sections)

        kc, vc = cache["k"][layer], cache["v"][layer]   # (NB, bs, KV, hd[/2])
        bt = cache["block_tables"][rows]                # (B, nb)
        bs = kc.shape[1]
        nb = bt.shape[1]
        Tc = nb * bs                                    # tokens per sequence
        quant = "k_scale" in cache
        oracle = not quant and "kv_qmax" in cache
        if quant:
            # dequantize the gathered context: codes · per-(token, head)
            # scale — identical f32 values to what the oracle pool stores
            ksc, vsc = cache["k_scale"][layer], cache["v_scale"][layer]
            kcg = kc[bt].reshape(B, Tc, KV, -1)
            vcg = vc[bt].reshape(B, Tc, KV, -1)
            if kc.dtype == jnp.uint8:
                kcg, vcg = kv_unpack_int4(kcg), kv_unpack_int4(vcg)
            k_ctx = kv_dequantize(kcg, ksc[bt].reshape(B, Tc, KV))
            v_ctx = kv_dequantize(vcg, vsc[bt].reshape(B, Tc, KV))
        else:
            k_ctx = kc[bt].reshape(B, Tc, KV, hd)
            v_ctx = vc[bt].reshape(B, Tc, KV, hd)
        # the cache stores QDQ values (codes·scale, or the oracle's fp copy
        # of the same product), so in-chunk keys must attend through the
        # SAME quantize-dequantize — otherwise a token scored inside a
        # chunk (prefill / spec verify) diverges from the identical token
        # scored one decode step later, breaking verify ≡ decode parity
        k_att, v_att = k, v
        if quant:
            qmax = cache["kv_qmax"][layer]
            k_codes, k_sc = kv_quantize(k, qmax)        # (B, C, KV, hd), (B, C, KV)
            v_codes, v_sc = kv_quantize(v, qmax)
            k_att = kv_dequantize(k_codes, k_sc)
            v_att = kv_dequantize(v_codes, v_sc)
        elif oracle:
            k_att = kv_qdq(k, cache["kv_qmax"][layer])
            v_att = kv_qdq(v, cache["kv_qmax"][layer])
        s_idx = jnp.arange(Tc, dtype=jnp.int32)[None, :]
        if window is None:
            ctx_pos = jnp.where(s_idx < starts[:, None], s_idx, -1)
        else:
            # ring: slot s holds the youngest token p ≡ s (mod Tc), p < start
            p_tok = starts[:, None] - 1 - ((starts[:, None] - 1 - s_idx) % Tc)
            ctx_pos = jnp.where(p_tok >= 0, p_tok, -1)
        out = chunk_attention(q, k_ctx, v_ctx, ctx_pos, k_att, v_att,
                              positions, window=window)

        i_idx = jnp.arange(C, dtype=jnp.int32)[None, :]
        logical = positions
        if window is not None:
            logical = logical % Tc
        blk = jnp.take_along_axis(bt, jnp.clip(logical // bs, 0, nb - 1),
                                  axis=1)
        phys = jnp.where(i_idx < valids[:, None], blk, kc.shape[0])  # OOB -> dropped
        if quant:
            # codes/scales computed above (the chunk attended their QDQ)
            if kc.dtype == jnp.uint8:
                k_codes, v_codes = kv_pack_int4(k_codes), kv_pack_int4(v_codes)
            kc = kc.at[phys, logical % bs].set(k_codes, mode="drop")
            vc = vc.at[phys, logical % bs].set(v_codes, mode="drop")
            ksc = ksc.at[phys, logical % bs].set(k_sc, mode="drop")
            vsc = vsc.at[phys, logical % bs].set(v_sc, mode="drop")
            cache["k_scale"] = cache["k_scale"].at[layer].set(ksc)
            cache["v_scale"] = cache["v_scale"].at[layer].set(vsc)
        else:
            # oracle writes the QDQ values it attended; fp writes raw k/v
            k_w, v_w = (k_att, v_att) if oracle else (k, v)
            kc = kc.at[phys, logical % bs].set(k_w.astype(kc.dtype), mode="drop")
            vc = vc.at[phys, logical % bs].set(v_w.astype(vc.dtype), mode="drop")
        cache["k"] = cache["k"].at[layer].set(kc)
        cache["v"] = cache["v"].at[layer].set(vc)

        out = out.reshape(B, C, H * hd)
        out = apply_linear(out, p["attn"]["wo"])
        return x + constrain(out, batch_axes(), seq_axis(), None)

    def _chunk_ssm(self, x, p, cache, layer, rows, starts, valids):
        """Hybrid SSM branch over a chunk, carrying each row's cached
        state; padding tokens are masked out of the state update.  A row's
        first chunk (start == 0) zeros the carried state — a freshly
        admitted sequence may be reusing a row whose previous occupant's
        final state is still in the cache."""
        h = rms_norm(x, p["ln1"], self.cfg.norm_eps)
        continuing = (starts > 0)[:, None, None]
        state = {"h": jnp.where(continuing, cache["ssm_h"][layer, rows], 0.0),
                 "conv": jnp.where(continuing, cache["ssm_conv"][layer, rows],
                                   0).astype(cache["ssm_conv"].dtype)}
        y, st = mamba_mod.mamba_forward(
            h, p["ssm"], chunk=self.cfg.chunk_size, return_state=True,
            init_state=state, valid=valids)
        cache["ssm_h"] = cache["ssm_h"].at[layer, rows].set(st["h"])
        cache["ssm_conv"] = cache["ssm_conv"].at[layer, rows].set(
            st["conv"].astype(cache["ssm_conv"].dtype))
        return y

    def _chunk_body(self, params, cache, tokens, rows, starts, valids):
        """Shared fixed-shape chunk forward over pooled-cache rows.

        ``tokens`` (B, C) int32, garbage past each lane's ``valid``;
        ``rows``/``starts``/``valids`` are (B,) int32 *data* mapping batch
        lane -> pool row / tokens already cached / live chunk length, so
        one executable serves every (prompt length × chunk index × batch
        composition).  Both the admission prefill (B = 1, one row) and the
        speculative verifier (B = every pool row) lower through this body.
        Returns (final-norm hidden (B, C, D), cache).
        """
        cfg = self.cfg
        cache = dict(cache)
        B, C = tokens.shape
        h = self._embed_in(params, tokens, None)
        positions = starts[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
        pos_in = (jnp.broadcast_to(positions[None], (3, B, C))
                  if cfg.rope == "mrope" else positions)
        if cfg.rope == "abs_sin":
            h = h + self._abs_sin(positions, cfg.d_model).astype(h.dtype)
        for l in range(cfg.num_layers):
            p = self._layer_slice(params, l)
            if cfg.family == "hybrid":
                a = self._chunk_attn(h, p, positions, pos_in, cache, l, rows,
                                     starts, valids) - h
                s = self._chunk_ssm(h, p, cache, l, rows, starts, valids)
                mix = jax.nn.sigmoid(p["mix"]).astype(h.dtype)
                h = h + mix * a + (1.0 - mix) * s
            else:
                h = self._chunk_attn(h, p, positions, pos_in, cache, l, rows,
                                     starts, valids)
            h, _ = self._ffn(h, p, exact=True)
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        cache["length"] = cache["length"].at[rows].set(starts + valids)
        return h, cache

    def prefill_chunk(self, params, cache, tokens, seq, start, valid):
        """One fixed-shape prompt chunk into pooled-cache row ``seq``.

        ``tokens``: (1, C) int32, garbage past ``valid``; ``start`` tokens
        of this sequence are already cached.  ``seq``/``start``/``valid``
        enter as data, so ONE executable serves every (prompt length ×
        chunk index) combination — the compile-churn fix chunked prefill
        exists for.  Returns (logits (1, 1, V) f32 for the last *valid*
        token — the only row an admission ever reads — and the cache).
        """
        h, cache = self._chunk_body(
            params, cache, tokens,
            jnp.asarray(seq, jnp.int32).reshape(1),
            jnp.asarray(start, jnp.int32).reshape(1),
            jnp.asarray(valid, jnp.int32).reshape(1))
        # only the last valid token's logits are ever consumed: slice the
        # hidden state BEFORE the d_model x V readout (a C-wide vocab
        # matmul per chunk otherwise, discarded for all but the last chunk)
        last = jax.lax.dynamic_slice_in_dim(h, valid - 1, 1, axis=1)
        logits = self._readout(params, last)
        return logits, cache

    def verify_chunk(self, params, cache, tokens, starts, valids):
        """Score a speculative window for EVERY pool row in one batched
        fixed-shape call (the chunked verifier behind ``repro.spec``).

        ``tokens`` (B, C): lane r is pool row r — [last committed token,
        draft_1..draft_k, garbage pad]; ``starts``/``valids`` (B,) data
        (valid = 0 marks a dead lane: its reads are masked, its writes
        drop to the garbage block).  Returns (logits (B, C, V) f32 at
        *every* position — index j scores the continuation after
        tokens[:, :j+1] — and the cache, with target-model K/V now written
        for all valid positions of the window).
        """
        B = tokens.shape[0]
        h, cache = self._chunk_body(
            params, cache, tokens, jnp.arange(B, dtype=jnp.int32),
            starts, valids)
        return self._readout(params, h), cache

    # ------------------------------------------------------------ quant API
    def quant_groups(self, seq_len: int = 4096) -> list[QuantGroup]:
        """Ordered weight groups for the ReLeQ episode (embed first,
        lm_head last, matching the paper's layer walk)."""
        cfg = self.cfg
        D, H, KV, hd, F = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.hd, cfg.d_ff
        groups: list[QuantGroup] = []

        def add(name, path, layer, shape, macs_per_token):
            nw = math.prod(shape)
            groups.append(QuantGroup(name, path, layer, tuple(shape), nw,
                                     int(macs_per_token * seq_len)))

        add("embed", ("embed",), None, (cfg.vocab_size, D), 0)
        for l in range(cfg.num_layers):
            s, pre = l % self.n_sub, f"L{l:02d}."
            base = ("blocks", s)
            add(pre + "attn.wq", base + ("attn", "wq"), l // self.n_sub, (D, H * hd), D * H * hd)
            add(pre + "attn.wk", base + ("attn", "wk"), l // self.n_sub, (D, KV * hd), D * KV * hd)
            add(pre + "attn.wv", base + ("attn", "wv"), l // self.n_sub, (D, KV * hd), D * KV * hd)
            add(pre + "attn.wo", base + ("attn", "wo"), l // self.n_sub, (H * hd, D), D * H * hd)
            is_moe = cfg.num_experts and s == self.n_sub - 1
            if is_moe:
                E, k = cfg.num_experts, cfg.experts_per_token
                active = D * F * k  # per token, per matrix
                add(pre + "moe.wg", base + ("moe", "wg"), l // self.n_sub, (E, D, F), active)
                add(pre + "moe.wu", base + ("moe", "wu"), l // self.n_sub, (E, D, F), active)
                add(pre + "moe.wd", base + ("moe", "wd"), l // self.n_sub, (E, F, D), active)
                if cfg.shared_expert:
                    for m, sh in (("wg", (D, F)), ("wu", (D, F)), ("wd", (F, D))):
                        add(pre + f"shared.{m}", base + ("shared", m), l // self.n_sub, sh, D * F)
            else:
                add(pre + "mlp.wg", base + ("mlp", "wg"), l // self.n_sub, (D, F), D * F)
                if cfg.act == "swiglu":
                    add(pre + "mlp.wu", base + ("mlp", "wu"), l // self.n_sub, (D, F), D * F)
                add(pre + "mlp.wd", base + ("mlp", "wd"), l // self.n_sub, (F, D), D * F)
            if cfg.family == "hybrid":
                Di = cfg.ssm_expand * D
                add(pre + "ssm.in_x", base + ("ssm", "in_x"), l // self.n_sub, (D, Di), D * Di)
                add(pre + "ssm.in_z", base + ("ssm", "in_z"), l // self.n_sub, (D, Di), D * Di)
                add(pre + "ssm.out", base + ("ssm", "out"), l // self.n_sub, (Di, D), D * Di)
        if not cfg.tie_embeddings:
            add("lm_head", ("lm_head",), None, (D, cfg.vocab_size), D * cfg.vocab_size)
        return groups

    def kv_quant_groups(self, seq_len: int = 4096) -> list[QuantGroup]:
        """Per-layer KV-cache bitwidth groups (HAQ-style): one pseudo-group
        per layer named ``kv.L{l:02d}`` whose "weights" are the K+V token
        activations a sequence of ``seq_len`` stores for that layer.
        ``n_macs=0`` — KV bits buy cache *bytes* (and decode bandwidth),
        not multiply precision, so the cost model sees them purely through
        the memory term.  ``path=("kv", l)`` is virtual: these groups are
        consumed by the serving engine's ``kv_bits`` knob, never by the
        params pytree."""
        cfg = self.cfg
        kv_hd = cfg.num_kv_heads * cfg.hd
        return [QuantGroup(f"kv.L{l:02d}", ("kv", l), l,
                           (seq_len, cfg.num_kv_heads, cfg.hd),
                           2 * seq_len * kv_hd, 0)
                for l in range(cfg.num_layers)]

    def frozen_bits(self) -> dict[str, int]:
        """Groups the agent may not touch (kept at 8 bits), per config."""
        out = {}
        for g in self.quant_groups():
            if any(g.name.startswith(p) or p in g.name for p in self.cfg.frozen_at_8):
                out[g.name] = 8
        return out
