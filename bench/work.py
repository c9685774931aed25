"""The useful work of a run, from the recorder: what the readers divide
device time or peak by.

A request's first token comes from its admission prefill; output token
``j >= 1`` comes from a decode step that attends ``prompt + j`` keys.
The operations of each come from the configuration's reference module,
so every model family counts its own work.
"""
from __future__ import annotations


def prefill_prompts(run):
    """Prompt lengths of the requests whose first token (the admission
    prefill's) was delivered inside the traced window."""
    for tr in run.rec.trails.values():
        if tr.token_times and run.in_trace(tr.token_times[0]):
            yield tr.prompt_len


def decode_tokens(run):
    """Keys attended by each decode-produced token delivered inside the
    traced window."""
    for tr in run.rec.trails.values():
        for j in range(1, len(tr.tokens)):
            if run.in_trace(tr.token_times[j]):
                yield tr.prompt_len + j


def useful_flops(run):
    """Prompt and delivered tokens through the model."""
    ref = run.reference
    return (sum(ref.prefill_flops(run.model, n)
                for n in prefill_prompts(run))
            + sum(ref.decode_flops(run.model, keys)
                  for keys in decode_tokens(run)))
