"""Rotary position embeddings: standard, partial-fraction, and M-RoPE.

M-RoPE (Qwen2-VL, arXiv:2409.12191): head_dim frequencies are split into
(temporal, height, width) sections; each section rotates with its own
position stream.  For text-only tokens all three streams carry the same
position, which reproduces 1-D RoPE exactly — that is the backbone behaviour
exercised here (the vision frontend is a stub per the assignment).

YaRN (arXiv:2309.00071, as DeepSeek-V2/V3 configure it): frequencies
below the ``beta_slow`` band are interpolated by ``factor``, those above
``beta_fast`` kept, and a linear ramp blends the dimensions between;
attention logits are scaled by ``yarn_mscale(factor, mscale_all_dim)``
squared.
"""
from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np


def rope_freqs(dim, theta=10000.0):
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_bounds(dim, theta, original_max_pos, beta_fast, beta_slow):
    """(low, high): the rotary dimensions where the ramp starts and ends."""
    def dim_of(n_rot):
        return (dim * math.log(original_max_pos / (n_rot * 2 * math.pi))
                / (2 * math.log(theta)))
    return (max(math.floor(dim_of(beta_fast)), 0),
            min(math.ceil(dim_of(beta_slow)), dim - 1))


def yarn_freqs(dim, theta, factor, original_max_pos, beta_fast, beta_slow):
    """The (dim/2,) YaRN inverse frequencies."""
    low, high = yarn_bounds(dim, theta, original_max_pos, beta_fast,
                            beta_slow)
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                   0, 1)
    return jnp.asarray(extra / factor * ramp + extra * (1 - ramp),
                       jnp.float32)


def cfg_rope(cfg, dim):
    """(inverse frequencies, cos/sin scale) of a config's ``dim``-wide
    rotary part: plain RoPE, or YaRN where ``cfg.yarn_factor`` > 1."""
    f = cfg.yarn_factor
    if f <= 1:
        return rope_freqs(dim, cfg.rope_theta), 1.0
    return (yarn_freqs(dim, cfg.rope_theta, f, cfg.yarn_original_max_pos,
                       cfg.yarn_beta_fast, cfg.yarn_beta_slow),
            yarn_mscale(f, cfg.yarn_mscale)
            / yarn_mscale(f, cfg.yarn_mscale_all_dim))


def _rotate(x, cos, sin):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def apply_rope(x, positions, *, theta=10000.0, fraction=1.0, freqs=None,
               mscale=1.0):
    """x: (B, S, H, hd); positions: (B, S) int32.

    ``fraction`` < 1 rotates only the first ``fraction * hd`` dims
    (StableLM-2 style partial rotary).  ``freqs`` (rot/2,) replaces the
    plain inverse frequencies (YaRN), ``mscale`` scales cos and sin.
    """
    hd = x.shape[-1]
    rot = int(hd * fraction)
    rot -= rot % 2
    xr, xp = x[..., :rot], x[..., rot:]
    if freqs is None:
        freqs = rope_freqs(rot, theta)  # (rot/2,)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (B,S,rot/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    cos = cos[:, :, None, :].astype(x.dtype)
    sin = sin[:, :, None, :].astype(x.dtype)
    xr = _rotate(xr, cos, sin)
    return jnp.concatenate([xr, xp], axis=-1) if rot < hd else xr


def apply_mrope(x, positions3, *, theta=10000.0, sections=(16, 24, 24)):
    """x: (B, S, H, hd); positions3: (3, B, S) — (t, h, w) position streams.

    ``sections`` are half-dim section sizes (sum == hd // 2), Qwen2-VL layout.
    """
    hd = x.shape[-1]
    assert sum(sections) == hd // 2, (sections, hd)
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    ang_all = positions3.astype(jnp.float32)[..., None] * freqs  # (3,B,S,hd/2)
    pieces = []
    off = 0
    for i, sec in enumerate(sections):
        pieces.append(ang_all[i, :, :, off:off + sec])
        off += sec
    ang = jnp.concatenate(pieces, axis=-1)  # (B,S,hd/2)
    cos = jnp.cos(ang)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, :, None, :].astype(x.dtype)
    return _rotate(x, cos, sin)
