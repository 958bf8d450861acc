"""Model assembly: every assigned architecture as one ``Model`` object.

Uniform stacks (dense / moe / ssm / enc-dec) scan over layers with stacked
(L, ...) parameters and a rematerialized block body (compile time and HBM stay
flat in depth — essential for the 61-layer / 64-layer archs). The 1:2 hybrid
(RecurrentGemma) uses a python loop over its heterogeneous 26 layers.

A ``Model`` exposes:
    init(key)                         -> (params, logical_axes)
    loss(params, batch)               -> (scalar loss, aux dict)
    prefill(params, batch, capacity)  -> (last-token logits, decode state)
    decode_step(params, state, token, pos) -> (logits, state)
    init_decode_state(batch, capacity, dtype) -> zeroed decode state
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models import common, moe, rglru, rwkv
from repro.distributed.sharding import constrain

Array = jnp.ndarray
Pytree = Any

MOE_LB_COEF = 0.01
MOE_Z_COEF = 1e-3


# ---------------------------------------------------------------------------
# per-layer-kind init / apply
# ---------------------------------------------------------------------------


def _init_block(cfg: ArchConfig, store: common.ParamStore, kind: str, stacked: int):
    D, F = cfg.d_model, cfg.d_ff
    if kind == "ssm":
        rwkv.init_rwkv_block(cfg, store, stacked=stacked)
        return
    if kind == "rec":
        rglru.init_rglru_block(cfg, store, stacked=stacked)
        common.init_norm(cfg, store, "ln_mlp", D, stacked=stacked)
        common.init_swiglu(store, D, F, stacked=stacked)
        return
    # attention-bearing kinds
    common.init_norm(cfg, store, "ln_attn", D, stacked=stacked)
    attn.init_attention(cfg, store, stacked=stacked)
    if kind == "encdec_dec":
        common.init_norm(cfg, store, "ln_cross", D, stacked=stacked)
        attn.init_attention(cfg, store, stacked=stacked, prefix="cross")
    common.init_norm(cfg, store, "ln_mlp", D, stacked=stacked)
    if kind == "moe":
        moe.init_moe(cfg, store, stacked=stacked)
    elif cfg.norm == "layernorm":  # whisper-style GELU MLP
        common.init_gelu_mlp(store, D, F, stacked=stacked)
    else:
        common.init_swiglu(store, D, F, stacked=stacked)


def _apply_mlp(cfg, p, x, dtype):
    with jax.named_scope("mlp"):
        xn = common.apply_norm(cfg, x, p, "ln_mlp")
        if "mlp_gate" in p:
            return x + common.swiglu(p, xn, dtype)
        return x + common.gelu_mlp(p, xn, dtype)


def _block_train(
    cfg, p, x, positions, kind, *, dtype, window, enc_out=None, enc_pos=None
):
    """One block forward (training). Returns (x, aux)."""
    aux: Dict[str, Array] = {}
    if kind == "ssm":
        B = x.shape[0]
        state = rwkv.init_rwkv_state(cfg, B)
        x, _ = rwkv.rwkv_block_train(cfg, p, x, state, dtype=dtype)
        return x, aux
    if kind == "rec":
        B = x.shape[0]
        state = rglru.init_rglru_state(cfg, B)
        x, _ = rglru.rglru_block(cfg, p, x, state, dtype=dtype)
        return _apply_mlp(cfg, p, x, dtype), aux
    causal = kind != "enc"
    with jax.named_scope("attn"):
        xn = common.apply_norm(cfg, x, p, "ln_attn")
        x = x + attn.attention_train(
            cfg, p, xn, positions, dtype=dtype, causal=causal, window=window,
            rope=kind not in ("enc", "encdec_dec"),  # enc-dec uses sinusoidal
        )
    if kind == "encdec_dec":
        xn = common.apply_norm(cfg, x, p, "ln_cross")
        x = x + attn.attention_train(
            cfg, p, xn, positions, dtype=dtype, kv_x=enc_out,
            kv_positions=enc_pos, prefix="cross",
        )
    if kind == "moe":
        xn = common.apply_norm(cfg, x, p, "ln_mlp")
        h, aux = moe.moe_ffn(cfg, p, xn, dtype=dtype)
        return x + h, aux
    return _apply_mlp(cfg, p, x, dtype), aux


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Model:
    cfg: ArchConfig
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    loss_chunk: int = 512
    decode_window: Optional[int] = None  # override cache window (long_500k)

    # ---------------- init ----------------

    def init(self, key: Optional[Array], abstract: bool = False) -> Tuple[Pytree, Pytree]:
        cfg = self.cfg
        store = common.ParamStore(key, self.param_dtype, abstract=abstract)
        common.init_embeddings(cfg, store)
        common.init_norm(cfg, store, "ln_final", cfg.d_model)
        kinds = cfg._layer_kinds()
        if cfg.is_encdec:
            enc = store.subtree("encoder")
            _init_block(cfg, enc, "enc", stacked=cfg.encoder_layers)
            common.init_norm(cfg, enc, "ln_enc_final", cfg.d_model)
            dec = store.subtree("decoder")
            _init_block(cfg, dec, "encdec_dec", stacked=cfg.n_layers)
        elif cfg.arch_type == "hybrid":
            # scan over repeating pattern units (e.g. rec,rec,attn) with the
            # remainder layers unrolled — compile time stays O(pattern), not
            # O(n_layers), which matters on the production dry-run.
            n_units, tail_kinds = _hybrid_units(cfg)
            units = store.subtree("units")
            for pos, kind in enumerate(cfg.hybrid_pattern):
                sub = units.subtree(f"u{pos}_{kind}")
                _init_block(cfg, sub, kind, stacked=n_units)
            tail = store.subtree("tail")
            for i, kind in enumerate(tail_kinds):
                sub = tail.subtree(f"layer_{i}_{kind}")
                _init_block(cfg, sub, kind, stacked=0)
        else:
            blocks = store.subtree("blocks")
            _init_block(cfg, blocks, kinds[0], stacked=cfg.n_layers)
        return store.params, store.axes

    # ---------------- shared helpers ----------------

    def _window(self, kind: str) -> Optional[int]:
        cfg = self.cfg
        if kind == "attn" and cfg.arch_type == "hybrid":
            return cfg.local_window
        return cfg.sliding_window

    def _embed_inputs(self, params, batch) -> Tuple[Array, Array, Array, Array]:
        """Returns (hidden, positions, labels, mask) with any multimodal prefix."""
        cfg = self.cfg
        dt = self.compute_dtype
        tokens = batch["tokens"]
        with jax.named_scope("embed"):
            x = common.embed_tokens(params, tokens, dt)
            labels, mask = batch["labels"], batch["mask"].astype(jnp.float32)
            if cfg.arch_type == "vlm":
                vis = batch["vision"].astype(dt)  # (B, Tv, D) stub patch embeddings
                x = jnp.concatenate([vis, x], axis=1)
                zeros = jnp.zeros(vis.shape[:2], labels.dtype)
                labels = jnp.concatenate([zeros, labels], axis=1)
                mask = jnp.concatenate(
                    [jnp.zeros(vis.shape[:2], jnp.float32), mask], axis=1
                )
            positions = jnp.arange(x.shape[1], dtype=jnp.int32)
        return x, positions, labels, mask

    def _encode(self, params, frames: Array) -> Tuple[Array, Array]:
        """Whisper encoder over stub frame embeddings. frames: (B, T, D)."""
        cfg = self.cfg
        dt = self.compute_dtype
        T = frames.shape[1]
        x = frames.astype(dt) + common.sinusoidal_positions(T, cfg.d_model).astype(dt)
        pos = jnp.arange(T, dtype=jnp.int32)
        ep = params["encoder"]

        def body(x, pl):
            x, _ = _block_train(cfg, pl, x, pos, "enc", dtype=dt, window=None)
            return x, None

        x, _ = jax.lax.scan(jax.checkpoint(body), x, ep_layers(ep))
        x = common.apply_norm(cfg, x, {"ln_enc_final_scale": ep["ln_enc_final_scale"],
                                       **_maybe_bias(ep, "ln_enc_final")}, "ln_enc_final")
        return x, pos

    # ---------------- training loss ----------------

    def loss(self, params, batch) -> Tuple[Array, Dict[str, Array]]:
        cfg = self.cfg
        dt = self.compute_dtype
        aux_total: Dict[str, Array] = {}

        if cfg.is_encdec:
            enc_out, enc_pos = self._encode(params, batch["frames"])
            x = common.embed_tokens(params, batch["tokens"], dt)
            x = x + common.sinusoidal_positions(x.shape[1], cfg.d_model).astype(dt)
            positions = jnp.arange(x.shape[1], dtype=jnp.int32)
            labels, mask = batch["labels"], batch["mask"].astype(jnp.float32)
            dp = params["decoder"]

            def body(x, pl):
                x, _ = _block_train(cfg, pl, x, positions, "encdec_dec", dtype=dt,
                                    window=cfg.sliding_window, enc_out=enc_out,
                                    enc_pos=enc_pos)
                return x, None

            x, _ = jax.lax.scan(jax.checkpoint(body), x, ep_layers(dp))
        else:
            x, positions, labels, mask = self._embed_inputs(params, batch)
            x = constrain(x, None, None, None)
            if cfg.arch_type == "hybrid":
                n_units, tail_kinds = _hybrid_units(cfg)

                def unit_body(x, up):
                    for pos, kind in enumerate(cfg.hybrid_pattern):
                        pl = up[f"u{pos}_{kind}"]
                        x, _ = _block_train(cfg, pl, x, positions, kind,
                                            dtype=dt, window=self._window(kind))
                    return x, None

                x, _ = jax.lax.scan(jax.checkpoint(unit_body), x, params["units"])
                for i, kind in enumerate(tail_kinds):
                    pl = params["tail"][f"layer_{i}_{kind}"]
                    x, _ = _block_train(cfg, pl, x, positions, kind, dtype=dt,
                                        window=self._window(kind))
            else:
                kind = cfg._layer_kinds()[0]
                window = self._window(kind)

                def body(x, pl):
                    x, aux = _block_train(cfg, pl, x, positions, kind, dtype=dt,
                                          window=window)
                    return x, aux

                x, aux_stack = jax.lax.scan(
                    jax.checkpoint(body), x, params["blocks"]
                )
                aux_total = {k: jnp.mean(v) for k, v in aux_stack.items()}

        with jax.named_scope("loss"):
            x = common.apply_norm(cfg, x, params, "ln_final")
            nll = common.chunked_xent(params, x, labels, mask, self.loss_chunk, dt)
        total = nll
        if "moe_lb_loss" in aux_total:
            total = total + MOE_LB_COEF * aux_total["moe_lb_loss"]
            total = total + MOE_Z_COEF * aux_total["moe_z_loss"]
        aux_total["nll"] = nll
        return total, aux_total

    # ---------------- decode ----------------

    def _cache_capacity(self, seq_len: int, kind: str) -> int:
        window = self.decode_window or self._window(kind)
        if window is not None:
            return min(seq_len, window)
        return seq_len

    def init_decode_state(self, batch: int, seq_len: int) -> Pytree:
        """Zeroed decode caches sized for a ``seq_len`` context."""
        cfg = self.cfg
        dt = self.compute_dtype
        kinds = cfg._layer_kinds()
        if cfg.is_encdec:
            cap = self._cache_capacity(seq_len, "attn")
            self_c = _stack_caches(cfg, cfg.n_layers, batch, cap, dt)
            cross_c = _stack_caches(cfg, cfg.n_layers, batch, cfg.encoder_seq, dt)
            return {"self": self_c, "cross": cross_c}
        if cfg.arch_type == "ssm":
            states = [rwkv.init_rwkv_state(cfg, batch) for _ in range(cfg.n_layers)]
            return {"ssm": jax.tree.map(lambda *xs: jnp.stack(xs), *states)}
        if cfg.arch_type == "hybrid":
            n_units, tail_kinds = _hybrid_units(cfg)

            def one(kind):
                if kind == "rec":
                    return rglru.init_rglru_state(cfg, batch)
                cap = self._cache_capacity(seq_len, kind)
                return attn.init_cache(cfg, batch, cap, dt)

            units = {
                f"u{pos}_{kind}": jax.tree.map(
                    lambda x: jnp.broadcast_to(x[None], (n_units,) + x.shape),
                    one(kind),
                )
                for pos, kind in enumerate(cfg.hybrid_pattern)
            }
            tail = [one(kind) for kind in tail_kinds]
            return {"units": units, "tail": tail}
        cap = self._cache_capacity(seq_len, kinds[0])
        return {"kv": _stack_caches(cfg, cfg.n_layers, batch, cap, dt)}

    def prefill(self, params, batch, seq_len: int) -> Tuple[Array, Pytree]:
        """Encode a full prompt, returning last-position logits + decode state."""
        cfg = self.cfg
        dt = self.compute_dtype
        state = self.init_decode_state(batch["tokens"].shape[0], seq_len)
        tokens = batch["tokens"]
        x = common.embed_tokens(params, tokens, dt)
        if cfg.arch_type == "vlm":
            x = jnp.concatenate([batch["vision"].astype(dt), x], axis=1)
        positions = jnp.arange(x.shape[1], dtype=jnp.int32)

        if cfg.is_encdec:
            enc_out, enc_pos = self._encode(params, batch["frames"])
            x = x + common.sinusoidal_positions(x.shape[1], cfg.d_model).astype(dt)
            cross = _build_cross_caches(cfg, params["decoder"], enc_out, enc_pos, dt)
            window = cfg.sliding_window

            def body(x, inp):
                pl, cache, crossc = inp
                xn = common.apply_norm(cfg, x, pl, "ln_attn")
                h, cache = attn.attention_prefill(cfg, pl, xn, positions, cache,
                                                  dtype=dt, window=window,
                                                  rope=False)
                x = x + h
                xn = common.apply_norm(cfg, x, pl, "ln_cross")
                # cross attention over encoder memory (read-only cache)
                h, _ = _cross_read(cfg, pl, xn, positions, crossc, dt)
                x = x + h
                x = _apply_mlp(cfg, pl, x, dt)
                return x, cache

            x, new_self = jax.lax.scan(
                jax.checkpoint(body), x, (params["decoder"], state["self"], cross)
            )
            state = {"self": new_self, "cross": cross}
        elif cfg.arch_type == "ssm":

            def body(x, inp):
                pl, st = inp
                x, st = rwkv.rwkv_block_train(cfg, pl, x, st, dtype=dt)
                return x, st

            x, new_states = jax.lax.scan(
                jax.checkpoint(body), x, (params["blocks"], state["ssm"])
            )
            state = {"ssm": new_states}
        elif cfg.arch_type == "hybrid":
            n_units, tail_kinds = _hybrid_units(cfg)

            def layer_prefill(pl, x, st, kind):
                if kind == "rec":
                    x, st = rglru.rglru_block(cfg, pl, x, st, dtype=dt)
                    return _apply_mlp(cfg, pl, x, dt), st
                xn = common.apply_norm(cfg, x, pl, "ln_attn")
                h, st = attn.attention_prefill(
                    cfg, pl, xn, positions, st, dtype=dt,
                    window=self._window(kind),
                )
                return _apply_mlp(cfg, pl, x + h, dt), st

            def unit_body(x, inp):
                up, ust = inp
                new = {}
                for pos, kind in enumerate(cfg.hybrid_pattern):
                    key = f"u{pos}_{kind}"
                    x, new[key] = layer_prefill(up[key], x, ust[key], kind)
                return x, new

            x, new_units = jax.lax.scan(
                jax.checkpoint(unit_body), x, (params["units"], state["units"])
            )
            new_tail = []
            for i, kind in enumerate(tail_kinds):
                pl = params["tail"][f"layer_{i}_{kind}"]
                x, st = layer_prefill(pl, x, state["tail"][i], kind)
                new_tail.append(st)
            state = {"units": new_units, "tail": new_tail}
        else:
            kind = cfg._layer_kinds()[0]
            window = self.decode_window or self._window(kind)

            def body(x, inp):
                pl, cache = inp
                xn = common.apply_norm(cfg, x, pl, "ln_attn")
                h, cache = attn.attention_prefill(cfg, pl, xn, positions, cache,
                                                  dtype=dt, window=window)
                x = x + h
                if kind == "moe":
                    xn = common.apply_norm(cfg, x, pl, "ln_mlp")
                    h, _ = moe.moe_ffn(cfg, pl, xn, dtype=dt)
                    x = x + h
                else:
                    x = _apply_mlp(cfg, pl, x, dt)
                return x, cache

            x, new_kv = jax.lax.scan(
                jax.checkpoint(body), x, (params["blocks"], state["kv"])
            )
            state = {"kv": new_kv}

        x = common.apply_norm(cfg, x, params, "ln_final")
        logits = common.lm_logits(params, x[:, -1:, :], dt)
        return logits[:, 0, :], state

    def decode_step(
        self, params, state, token: Array, pos: Array
    ) -> Tuple[Array, Pytree]:
        """One decode step. token: (B,) int32; pos: scalar int32."""
        cfg = self.cfg
        dt = self.compute_dtype
        x = common.embed_tokens(params, token[:, None], dt)  # (B, 1, D)

        if cfg.is_encdec:
            x = x + common.sinusoidal_positions_at(pos, cfg.d_model).astype(dt)
            window = self.decode_window or cfg.sliding_window

            def body(x, inp):
                pl, cache, crossc = inp
                xn = common.apply_norm(cfg, x, pl, "ln_attn")
                h, cache = attn.attention_decode(cfg, pl, xn, pos, cache, dtype=dt,
                                                 window=window, rope=False)
                x = x + h
                xn = common.apply_norm(cfg, x, pl, "ln_cross")
                h, _ = attn.attention_decode(cfg, pl, xn, pos, crossc, dtype=dt,
                                             update_cache=False, rope=False,
                                             causal=False, prefix="cross")
                x = x + h
                x = _apply_mlp(cfg, pl, x, dt)
                return x, cache

            x, new_self = jax.lax.scan(
                body, x, (params["decoder"], state["self"], state["cross"])
            )
            state = {"self": new_self, "cross": state["cross"]}
        elif cfg.arch_type == "ssm":

            def body(x, inp):
                pl, st = inp
                x, st = rwkv.rwkv_block_decode(cfg, pl, x, st, dtype=dt)
                return x, st

            x, new_states = jax.lax.scan(body, x, (params["blocks"], state["ssm"]))
            state = {"ssm": new_states}
        elif cfg.arch_type == "hybrid":
            n_units, tail_kinds = _hybrid_units(cfg)

            def layer_decode(pl, x, st, kind):
                if kind == "rec":
                    x, st = rglru.rglru_block(cfg, pl, x, st, dtype=dt)
                    return _apply_mlp(cfg, pl, x, dt), st
                xn = common.apply_norm(cfg, x, pl, "ln_attn")
                h, st = attn.attention_decode(
                    cfg, pl, xn, pos, st, dtype=dt, window=self._window(kind)
                )
                return _apply_mlp(cfg, pl, x + h, dt), st

            def unit_body(x, inp):
                up, ust = inp
                new = {}
                for p_, kind in enumerate(cfg.hybrid_pattern):
                    key = f"u{p_}_{kind}"
                    x, new[key] = layer_decode(up[key], x, ust[key], kind)
                return x, new

            x, new_units = jax.lax.scan(
                unit_body, x, (params["units"], state["units"])
            )
            new_tail = []
            for i, kind in enumerate(tail_kinds):
                pl = params["tail"][f"layer_{i}_{kind}"]
                x, st = layer_decode(pl, x, state["tail"][i], kind)
                new_tail.append(st)
            state = {"units": new_units, "tail": new_tail}
        else:
            kind = cfg._layer_kinds()[0]
            window = self.decode_window or self._window(kind)
            rope = True  # RoPE for all non-enc-dec archs (enc-dec = sinusoidal)

            def body(x, inp):
                pl, cache = inp
                xn = common.apply_norm(cfg, x, pl, "ln_attn")
                h, cache = attn.attention_decode(cfg, pl, xn, pos, cache, dtype=dt,
                                                 window=window, rope=rope)
                x = x + h
                if kind == "moe":
                    xn = common.apply_norm(cfg, x, pl, "ln_mlp")
                    h, _ = moe.moe_ffn(cfg, pl, xn, dtype=dt)
                    x = x + h
                else:
                    x = _apply_mlp(cfg, pl, x, dt)
                return x, cache

            x, new_kv = jax.lax.scan(body, x, (params["blocks"], state["kv"]))
            state = {"kv": new_kv}

        x = common.apply_norm(cfg, x, params, "ln_final")
        logits = common.lm_logits(params, x, dt)
        return logits[:, 0, :], state


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _hybrid_units(cfg) -> Tuple[int, Tuple[str, ...]]:
    """(n_full_units, leftover_kinds) for the hybrid pattern scan."""
    plen = len(cfg.hybrid_pattern)
    n_units = cfg.n_layers // plen
    tail = cfg._layer_kinds()[n_units * plen :]
    return n_units, tuple(tail)


def ep_layers(tree: Dict[str, Array]) -> Dict[str, Array]:
    """Layer-stacked param arrays only (drop final norms from the scan xs)."""
    return {k: v for k, v in tree.items() if not k.startswith("ln_enc_final")}


def _maybe_bias(tree, prefix):
    key = f"{prefix}_bias"
    return {key: tree[key]} if key in tree else {}


def _stack_caches(cfg, n_layers, batch, capacity, dtype):
    one = attn.init_cache(cfg, batch, capacity, dtype)
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n_layers,) + x.shape), one)


def _build_cross_caches(cfg, dec_params, enc_out, enc_pos, dtype):
    """Precompute per-layer cross K/V from the encoder output (stacked on L)."""
    KV, hd = cfg.n_kv_heads, cfg.hd
    B, T = enc_out.shape[:2]

    def per_layer(pl):
        k = (enc_out @ pl["cross_wk"].astype(dtype))
        v = (enc_out @ pl["cross_wv"].astype(dtype))
        if cfg.qkv_bias:
            k = k + pl["cross_bk"].astype(dtype)
            v = v + pl["cross_bv"].astype(dtype)
        return {
            "k": k.reshape(B, T, KV, hd),
            "v": v.reshape(B, T, KV, hd),
            "slot_pos": enc_pos.astype(jnp.int32),
        }

    return jax.vmap(per_layer)(dec_params)


def _cross_read(cfg, pl, xn, positions, crossc, dtype):
    """Full-sequence cross attention against a precomputed cross cache."""
    B, S, D = xn.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = xn @ pl["cross_wq"].astype(dtype)
    if cfg.qkv_bias:
        q = q + pl["cross_bq"].astype(dtype)
    q = q.reshape(B, S, H, hd)
    out = attn.attention_core(q, crossc["k"], crossc["v"], positions,
                              crossc["slot_pos"], causal=False, window=None)
    out = out.reshape(B, S, H * hd)
    return out @ pl["cross_wo"].astype(dtype), crossc
