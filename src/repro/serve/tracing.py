"""Names of the serve engine's host spans and device scopes.

Host spans are ``jax.profiler.TraceAnnotation``s named ``serve.<name>``:
they cost about a microsecond when no profile is being captured, and
while one is, they land on the profiler's host plane on the same clock
as the device's ops.  Device scopes are ``jax.named_scope`` names: HLO
metadata only, carried by every op traced inside them into the op's
``op_name`` path, so a profile can put each device op under the layer
that produced it.  Ops outside every scope (the layer scan's slicing of
stacked weights and caches, the decode loop's carry, copies the compiler
inserts) are state movement.
"""
from __future__ import annotations

import jax

# host spans (without the ``serve.`` prefix); a dotted name is a child of
# its prefix
SPANS = (
    "step",                # one iteration of the engine loop
    "sleep",               # realtime wait for the next arrival
    "evict",               # zero-evict retired slots, release pages
    "admit",               # one admission wave (n, uids)
    "admit.alloc",         # page allocation and the prefix probe
    "admit.prefill",       # one bucket group (bucket, n, npad)
    "admit.hits",          # the no-prefill prefix-hit path
    "admit.sync",          # blocking read of the first tokens
    "dispatch",            # enqueue one macro decode block
    "readback",            # read back one block and commit its tokens
    "readback.wait",       # blocking device-to-host read of the block
    "journal.flush",       # request journal flush
    "upgrade.grow",        # live upgrade: growth of the new weights
    "upgrade.prewarm",     # live upgrade: compiling the grown programs
    "upgrade.swap",        # live upgrade: quiesce, re-layout, re-admit
)

# device scopes of the transformer family's forward and the decode loop
SCOPES = (
    "embed",          # token embedding
    "attn.qkv",       # pre-attention norm, q/k/v projections, RoPE
    "attn.kv_write",  # writing this step's K/V into the cache
    "attn.core",      # the attention kernel, or gather plus attend
    "attn.out",       # output projection and residual
    "mlp",            # post-attention norm, MLP or MoE, residual
    "head",           # final norm and logits
    "select",         # NaN sentinel, argmax or sample, stopping
)


def span(name, **args):
    """Host span ``serve.<name>`` with ``args`` as its metadata."""
    return jax.profiler.TraceAnnotation("serve." + name, **args)


def scope(name):
    """Device scope ``name`` (one of ``SCOPES``)."""
    return jax.named_scope(name)
