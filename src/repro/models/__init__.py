"""Model zoo: family registry.

Every family module exposes the same functional interface:
  init(rng, cfg) -> params
  forward(params, batch, cfg) -> (logits, aux)
  param_specs(cfg) -> pytree of logical-axis tuples
  init_cache(cfg, batch, max_len) / prefill / decode_step   (decoders only)

Families that serve through the continuous-batching engine additionally
implement the SLOT-STATE PROTOCOL (see docs/serving.md):
  cache_specs(cfg)                 -> logical axes of the slot pool
  prefill_last(params, batch, cfg, cache)
      batch = {"tokens": (B, S) bucket-padded, "plens": (B,) true lengths}
      -> (logits (B, V) at each row's last real prompt position,
          cache after each row's REAL prompt)
  decode_step_slots(params, tokens, positions, cache, cfg, done=None)
      one token per slot at per-slot lengths; ``done`` rows are exact
      no-ops (frozen state / bit-identical cache re-stores)
  serve_supported(cfg) -> (ok, detail)

``cfg.decode_kernel`` selects the slot attention backend inside these
hooks: "jnp" (default) or a Pallas kernel mode ("auto" / "interpret" /
"reference" — see kernels/ops.py); caches are allocated in the TPU
pool layout (cache axis padded via ``common.pad_cache_len``) either way.

Families that additionally serve as a speculative draft/target implement
the chunk-verify extension of the protocol:
  verify_step_slots(params, tokens (B,S), positions (B,), cache, cfg,
                    done=None) -> (logits (B,S,V), pending)
      feed an S-token chunk per slot starting at each row's own length,
      logits at every chunk index, cache READ-ONLY;
  commit_slots(params, tokens, positions, n_feed (B,), cache, pending,
               cfg, done=None) -> cache
      realize exactly each row's first ``n_feed`` chunk feeds (accepted
      prefix) — deferred scatter for KV layouts, stacked-state gather for
      recurrent layouts; ``n_feed == 0`` / ``done`` rows are untouched.

Paging is part of the protocol too: ``paged_groups(cfg)`` declares which
top-level slot-cache groups re-lay as page arenas under ``--pool paged``
(see ``serve/paged.py``).  Every slot hook above must then accept groups
carrying a ``"bt"`` block table — writes resolve their page through the
table, ``done``/unallocated rows redirect to the page sentinel and drop.
A family with no declaration (or an empty one) serves dense, and the
engine reports the named ``pool_fallback_reason`` instead of silently
flipping the pool kind.
"""
from __future__ import annotations

import importlib

_FAMILIES = {
    "transformer": "repro.models.transformer",
    "griffin": "repro.models.griffin",
    "xlstm": "repro.models.xlstm",
}


def get_family(cfg_or_name):
    name = getattr(cfg_or_name, "family", cfg_or_name)
    return importlib.import_module(_FAMILIES[name])


def serve_supported(cfg):
    """Capability probe: can ``ContinuousBatchingEngine`` serve this config?

    Returns (ok, detail) — ``detail`` names the slot cache layout when
    servable, or the reason when not.  This replaces hard-coded family
    checks: a family opts in by implementing the slot-state protocol and
    its own ``serve_supported``.
    """
    fam = get_family(cfg)
    probe = getattr(fam, "serve_supported", None)
    if probe is None or not (hasattr(fam, "prefill_last")
                             and hasattr(fam, "decode_step_slots")):
        return False, (f"family {cfg.family!r} does not implement the "
                       "slot-state protocol")
    return probe(cfg)


def spec_decode_supported(cfg):
    """Capability probe: can this config run as a speculative draft or
    target?  Requires the slot-state protocol plus the chunk-verify hooks
    (``verify_step_slots`` / ``commit_slots``)."""
    ok, detail = serve_supported(cfg)
    if not ok:
        return ok, detail
    fam = get_family(cfg)
    if not (hasattr(fam, "verify_step_slots")
            and hasattr(fam, "commit_slots")):
        return False, (f"family {cfg.family!r} does not implement the "
                       "chunk-verify (speculative) slot hooks")
    return True, detail


def paged_groups(cfg):
    """Slot-state protocol: which slot-cache groups page under ``--pool
    paged``.

    Returns ``{top_level_cache_key: (kind, leaf_names)}`` where ``kind``
    is:
      * ``"seq"``  — the named leaves are (L, B, S, ...) sequence caches
        sharing one S axis; S splits into fixed pages and every slot
        holds a block table of page ids (transformer K/V, MLA latents,
        griffin local-attention rings).
      * ``"slot"`` — the named leaves are per-slot state with no sequence
        axis (xlstm conv shift tails); the whole per-slot tail is one
        page and the block table has a single entry.
    Leaves of a declared group NOT named stay dense-per-slot (xlstm's
    mLSTM C/n/m carries ride in the same group dict as its paged conv
    tail).  An empty dict means nothing pages — the engine serves dense
    and surfaces the family's ``pool_fallback_reason``.
    """
    fam = get_family(cfg)
    probe = getattr(fam, "paged_groups", None)
    return probe(cfg) if probe else {}


def slot_cache_layout(cfg):
    """Short layout tag for benchmarks/telemetry: how a serve slot stores
    its sequence state.  Dispatches to the family module (part of the
    slot-state protocol) — no hard-coded family switch here."""
    fam = get_family(cfg)
    probe = getattr(fam, "slot_cache_layout", None)
    return probe(cfg) if probe else "unsupported"
