"""The Mamba-2 mixer two families share (``models/granite_hybrid.py``,
``models/nemotron_h.py``): its float32 leaves and how a seed draws them,
the causal convolution with its tail (``causal_conv``, which
``models/lfm2.py``'s convolution operator calls too, with no bias and no
activation at three taps), the mixer itself over ``ops/ssd_scan.py`` at any
number of groups of B and C, and what the engine's seam reads of its state.

What a config has to say: ``dim``, ``mamba_n_heads``, ``mamba_d_head``,
``mamba_d_state``, ``mamba_n_groups``, ``mamba_d_conv``,
``mamba_chunk_size``, ``n_mamba`` (its Mamba layers), ``norm_eps``,
``w8a8_prefill``, ``dtype``, ``state_dtype``. What the state holds
(``init_mamba_state``): ``conv``, every Mamba layer's convolution tail, and
``ssm``, its recurrent state. A layer's parameters (``init_mamba_params``):
``mixer_norm``, in_proj in its three parts ``in_z | in_xbc | in_dt``,
``out_proj`` and ``MAMBA_VECTORS``.

The equations, for the normed input ``h`` of one layer (``G`` groups, head
``h`` reading group ``h // (heads / G)``):

- ``[z | xBC | dt] = h W_in`` (inner | inner + 2 G N | heads).
  ``xBC_t = silu(b_c + sum_j w_c[:, j] * xBC_{t-3+j})`` per channel, zeros
  before the row's first real token. ``xBC`` splits into ``X [heads, P]``,
  ``B [G, N]``, ``C [G, N]``. ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)`` per head. The recurrence is ``ops/ssd_scan.py``'s.
- Gate, then norm, **by group**: ``y = RMSNorm(Y * silu(z)) * w_n`` with
  the mean square taken over each of the G runs of ``inner / G`` channels
  (one group: over the whole inner width); ``y W_out``.
- **Left pads.** The engine pads rows on the left and a recurrence runs
  through pads. At a pad position ``h`` is zeroed before ``W_in`` (the
  caller's) and ``xBC`` again after the convolution, whose bias would leak:
  the state and the convolution's tail are exactly zero when the row's
  first real token arrives.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .llama import _cache_write, _proj


def d_inner(cfg) -> int:
    return cfg.mamba_n_heads * cfg.mamba_d_head


def conv_dim(cfg) -> int:
    """Channels the convolution runs over: X, and every group's B and C."""
    return d_inner(cfg) + 2 * cfg.mamba_n_groups * cfg.mamba_d_state


# the Mamba mixer's leaves that stay in float32 whatever the weights' type:
# the recurrence is sensitive to them and they are a few thousand numbers
MAMBA_VECTORS = ("conv_w", "conv_b", "dt_bias", "A_log", "D", "ssm_norm")


def init_mamba_vectors(key: jax.Array, cfg) -> dict:
    """What the scan is sensitive to, drawn as Mamba-2's published
    initialisation draws it, so that a seeded model decays as a trained one
    does: ``A_log = log(U[1, 16])``, ``dt_bias`` the inverse softplus of
    ``dt ~ logU[1e-3, 1e-1]``, ``D = 1``, the convolution
    ``U[-1/sqrt(d_conv), 1/sqrt(d_conv)]`` (a depth-wise ``Conv1d``'s
    default), a unit norm weight. All float32."""
    Lm, H, K = cfg.n_mamba, cfg.mamba_n_heads, cfg.mamba_d_conv
    ka, kd, kw, kb = jax.random.split(key, 4)
    f32 = jnp.float32
    dt = jnp.exp(jax.random.uniform(
        kd, (Lm, H), f32, jnp.log(1e-3), jnp.log(1e-1)))
    bound = K ** -0.5
    return {
        "conv_w": jax.random.uniform(kw, (Lm, conv_dim(cfg), K), f32,
                                     -bound, bound),
        "conv_b": jax.random.uniform(kb, (Lm, conv_dim(cfg)), f32,
                                     -bound, bound),
        # softplus(dt_bias) = dt
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(ka, (Lm, H), f32, 1.0, 16.0)),
        "D": jnp.ones((Lm, H), f32),
        "ssm_norm": jnp.ones((Lm, d_inner(cfg)), f32),
    }


def init_mamba_params(norm, key: jax.Array, cfg) -> dict:
    """The stacked Mamba layers' leaves; ``norm(shape)`` draws a matrix."""
    Lm, D = cfg.n_mamba, cfg.dim
    return {
        "mixer_norm": jnp.ones((Lm, D), cfg.dtype),
        # in_proj, a product a part (z | xBC | dt): no slice of a chunk's
        # wide output, and every width whole lane tiles but dt's
        "in_z": norm((Lm, D, d_inner(cfg))),
        "in_xbc": norm((Lm, D, conv_dim(cfg))),
        "in_dt": norm((Lm, D, cfg.mamba_n_heads)),
        "out_proj": norm((Lm, d_inner(cfg), D)),
        **init_mamba_vectors(key, cfg),
    }


def init_mamba_state(cfg, batch: int) -> dict:
    """Every Mamba layer's convolution tail, ``[mamba layers, B, d_conv - 1,
    channels]`` in the activations' type (channels on the lanes), and
    recurrent state, ``[mamba layers, B, N, heads * P]`` (``ops/ssd_scan.py``
    says why it is laid out so)."""
    return {
        "conv": jnp.zeros((cfg.n_mamba, batch, cfg.mamba_d_conv - 1,
                           conv_dim(cfg)), cfg.dtype),
        "ssm": jnp.zeros((cfg.n_mamba, batch, cfg.mamba_d_state,
                          d_inner(cfg)), cfg.state_dtype),
    }


def causal_conv(xbc, tail, w, b=None, act=jax.nn.silu):
    """Depth-wise causal convolution at any number of taps: xbc [B, S, C]
    after ``tail`` [B, K - 1, C], the K - 1 inputs before it; w [C, K];
    ``b`` [C] or None for no bias; ``act`` the activation on the sum or
    None for none. Returns (act(conv) [B, S, C] float32, the new tail).

    Who calls it: ``mamba_mixer`` below (Granite's and Nemotron-H's Mamba
    layers: four taps, a bias, silu, then the scan) and
    ``models/lfm2.py``'s convolution operator (three taps over the gated
    product, no bias, no activation: the convolution IS the mixer)."""
    K = w.shape[-1]
    S = xbc.shape[1]
    ext = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
    acc = None if b is None else b.astype(jnp.float32)
    for j in range(K):
        tap = w[:, j].astype(jnp.float32) * ext[:, j:j + S].astype(
            jnp.float32)
        acc = tap if acc is None else acc + tap
    return (acc if act is None else act(acc)), ext[:, S:]


def mamba_mixer(h, lp, slot, valid, cache, cfg, scan_kernels: bool,
                interpret: bool, cache_rows=None):
    """The Mamba-2 mixer over h [B, S, D] (normed, zero under the pad) at
    Mamba slot ``slot`` of the state. ``cache_rows`` [B] int32: h is a row
    piece of a batch whose state holds more rows (the engine's prefill,
    ``Family.prefill_piece_tokens``), and row b's convolution tail and
    recurrent state live at the state's batch row ``cache_rows[b]`` — read
    and written there in place, no slice of ``ssm`` made. The
    ``jax.named_scope`` names are metadata a device trace is read by
    (README "Device time by layer")."""
    B, S, _ = h.shape
    H, P, N = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    G, inner = cfg.mamba_n_groups, d_inner(cfg)
    aq = cfg.w8a8_prefill and S > 1
    f32 = jnp.float32
    with jax.named_scope("ssm_in"):
        z = _proj("bsd,de->bse", h, lp["in_z"], aq)
        xbc = _proj("bsd,de->bse", h, lp["in_xbc"], aq)
        dt = jax.nn.softplus(
            _proj("bsd,de->bse", h, lp["in_dt"], aq).astype(f32)
            + lp["dt_bias"])
    with jax.named_scope("conv"):
        tail = jax.lax.dynamic_index_in_dim(cache["conv"], slot, 0, False)
        if cache_rows is not None:
            tail = tail[cache_rows]
        xbc, tail = causal_conv(xbc, tail, lp["conv_w"], lp["conv_b"])
        # the bias would leak through a pad position
        xbc = jnp.where(valid[..., None], xbc, 0.0).astype(h.dtype)
        # the whole batch's tails in one update, a piece's a row at a time
        # at its own batch row: the KV cache's write, on [layers, B, 3, C]
        conv = _cache_write(cache["conv"], tail.astype(cache["conv"].dtype),
                            slot, 0, cache_rows)
    with jax.named_scope("ssd"):
        x = xbc[..., :inner].reshape(B, S, H, P)
        Bm = xbc[..., inner:inner + G * N].reshape(B, S, G, N)
        Cm = xbc[..., inner + G * N:].reshape(B, S, G, N)
        A = -jnp.exp(lp["A_log"].astype(f32))
        ssm = cache["ssm"]
        # imported on use, as llama's kernels: the dense families' paths
        # never load it
        from ..ops import ssd_scan

        if scan_kernels and ssm.dtype == f32:
            if S == 1:
                y, ssm = ssd_scan.ssm_decode_update(
                    x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], lp["D"], ssm,
                    slot, interpret=interpret)
                y = y[:, None]
            else:
                # left padding: a row's pads are its first positions
                pads = S - jnp.sum(valid, axis=-1, dtype=jnp.int32)
                y, ssm = ssd_scan.ssd_prefill_scan(
                    x, dt, A, Bm, Cm, lp["D"], ssm, slot, pads, cache_rows,
                    chunk=cfg.mamba_chunk_size, interpret=interpret)
        else:
            state = jax.lax.dynamic_index_in_dim(ssm, slot, 0, False).astype(
                f32)
            if S == 1:
                y, state = ssd_scan.ssm_step_xla(
                    x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], lp["D"], state)
                y = y[:, None]
            else:
                y, state = ssd_scan.ssd_chunked_xla(
                    x, dt, A, Bm, Cm, lp["D"], state, cfg.mamba_chunk_size,
                    cache_rows)
            ssm = jax.lax.dynamic_update_slice(
                ssm, state.astype(ssm.dtype)[None], (slot, 0, 0, 0))
    with jax.named_scope("ssm_out"):
        # gate, then norm, group by group of the inner width
        y = y.reshape(B, S, inner).astype(f32) * jax.nn.silu(z.astype(f32))
        # a group's run of lanes at a time: splitting the lane dim into
        # [G, inner / G] would copy the chunk into another layout
        runs = [y[..., g * (inner // G):(g + 1) * (inner // G)]
                for g in range(G)]
        runs = [r * jax.lax.rsqrt(
            jnp.mean(r * r, axis=-1, keepdims=True) + cfg.norm_eps)
            for r in runs]
        y = runs[0] if G == 1 else jnp.concatenate(runs, axis=-1)
        y = (y * lp["ssm_norm"]).astype(h.dtype)
        out = _proj("bse,ed->bsd", y, lp["out_proj"], aq)
    return out, dict(cache, conv=conv, ssm=ssm)


def last_state(cache: dict) -> jax.Array:
    """[2, B, N, heads * P]: the first and the last Mamba layer's recurrent
    state after the latest forward, so that a parity check sees the state
    and not the logits alone — the first layer's carries one product's
    rounding and the scan's own arithmetic, the last layer's everything
    before it."""
    return jnp.stack([cache["ssm"][0], cache["ssm"][-1]])


def prefill_counts(cfg, pad_lens, spans, cache_len=None) -> dict:
    """What the scan of one dispatch's prefill saw, from the pads it was
    packed with: real tokens x Mamba layers, and the tokens of the chunks
    ``ssd_prefill_scan`` did not skip x Mamba layers. ``spans`` are the
    prefill's query spans [lo, hi) over the bucket; a scan reads no cache,
    so ``cache_len`` is taken and not read."""
    import numpy as np

    from ..ops.ssd_scan import scan_tokens_computed

    pads = np.asarray(pad_lens, np.int64)
    real = computed = 0
    for lo, hi in spans:
        inside = np.clip(pads - lo, 0, hi - lo)   # pads among these tokens
        real += int(((hi - lo) - inside).sum())
        computed += scan_tokens_computed(inside, hi - lo,
                                         cfg.mamba_chunk_size)
    return {"scan_tokens_real": real * cfg.n_mamba,
            "scan_tokens_computed": computed * cfg.n_mamba}
