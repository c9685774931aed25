"""The control — the reference one precision step below the
configuration's, put in the program's place — reads above a limit that
exact serving meets, at test sizes on the CPU.

At these widths the benchmark's default weight scale leaves the models
predicting their input token by wide margins, so no rounding could flip
a token; the test draws the weights wider (std 0.05) to have near-ties,
as the published widths have at the default scale.
"""
import jax
import numpy as np
import pytest

import bench_tiny
from bench import check, common, harness, weights
from bench.recorder import Recorder
from bench.reference import transformer as ref
from repro.configs.base import get_config
from repro.models import get_family

LIMIT = 1e-6  # what exact serving meets on the CPU


def _greedy(spec, params, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        lg = ref.logits_at(spec, params, seq, [len(seq) - 1])
        seq.append(int(np.asarray(lg)[0].argmax()))
    return np.asarray(seq[len(prompt):], np.int32)


@pytest.mark.parametrize("arch, conf", [
    ("qwen1.5-0.5b-smoke", bench_tiny.QWEN),
])
def test_control_fails_the_limit_that_exact_serving_meets(
        arch, conf, monkeypatch):
    monkeypatch.setattr(weights, "STD", 0.05)
    cfg = get_config(arch)
    spec = ref.describe(conf)
    lower = check.LOWER[spec["dtype"]]
    served, control = [], []
    for seed in range(3):
        shapes = jax.eval_shape(lambda: get_family(cfg).init(
            jax.random.PRNGKey(0), cfg))
        params = weights.make_params(shapes, weights.seed_key(seed))
        prompt = np.random.default_rng(seed).integers(
            0, cfg.vocab_size, 16, dtype=np.int32)
        toks = _greedy(spec, params, prompt, 120)
        served.append(check.token_gaps(ref, spec, params, prompt, toks, 0,
                                       120))
        control.append(check.token_gaps(ref, spec, params, prompt, toks, 0,
                                        120, (lower, params)))
    assert check.mean(served) <= LIMIT
    assert check.mean(control) > LIMIT


def _served(sess, n):
    """A recorded run of ``n`` requests of the cell's own traffic through
    the program's engine, all run to completion."""
    rec = Recorder()
    eng = sess.engine(journal=rec)
    reqs = []
    for uid in range(n):
        r = sess.traffic.closed_request(uid, 0.0, True)
        rec.expect(r.uid, r.arrival, r.prompt_len, r.measured)
        reqs.append(sess.P["Request"](
            uid=uid, prompt=sess.traffic.tokens(uid, r.prompt_len),
            max_new_tokens=r.out_len))
    eng.run(reqs)
    return rec


# the control flips at least one sampled token on these seeds (on 7 of
# seeds 1-12 at this size)
@pytest.mark.parametrize("seed", [1, 2])
def test_control_through_the_harness_is_not_correct(seed, tmp_path,
                                                    monkeypatch):
    """A recorded CPU run judged by ``harness.compare``, as a cell's runs
    are: correct as served, not correct with the control in the
    program's place.  The control is the cell's own step (fp8 below
    bf16).  The limit is this size's: a 256-word vocabulary holds few
    near-ties, so the control reads under the cell's own limit here, and
    that limit is read at published widths on the chip, by
    ``bench/calibrate.py``."""
    monkeypatch.setattr(weights, "STD", 0.05)
    monkeypatch.setitem(check.LOWER, "float32", "fp8")
    root = bench_tiny.make_root(tmp_path)
    sess = harness.Session(common.Cell(root, "qwen-chat-batch"), seed, 1.0)
    rec = _served(sess, 16)
    limits = {"gap_mean": LIMIT}
    ok, numbers = harness.compare(sess, rec, limits)
    assert ok, numbers
    ok, numbers = harness.compare(sess, rec, limits, control=True)
    assert not ok, numbers
    assert numbers["gap_mean"]["value"] > numbers["gap_mean"]["limit"]
