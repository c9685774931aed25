"""Host spans of the serve engine and device scopes of its programs.

A CPU profile of a small engine run must hold the ``serve.*`` spans,
correctly nested, with the admitted uids on each admission wave, and no
span opened per token.  The compiled macro decode loop must put every
matmul, kernel call and scatter of a step under a name from
``tracing.SCOPES``, and leave the layer scan's slicing outside them.
"""
import ast
import collections
import glob
import pathlib
import re

import jax
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.models import get_family
from repro.serve import ContinuousBatchingEngine, Request, tracing
from repro.serve.upgrade import UpgradeManager

SRC = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"
Span = collections.namedtuple("Span", "name start end line args")

# the span each span must lie inside, on the same thread
PARENTS = {
    "sleep": ("step",), "evict": ("step",), "admit": ("step",),
    "dispatch": ("step",), "readback": ("step",),
    "upgrade.swap": ("step",),
    "admit.alloc": ("admit",), "admit.prefill": ("admit",),
    "admit.hits": ("admit",), "admit.sync": ("admit",),
    "readback.wait": ("readback",),
    "journal.flush": ("admit", "readback", "upgrade.swap"),
}


class _Journal:
    """The engine's journal hook, keeping nothing."""

    def record_tokens(self, uid, toks):
        pass

    def record_finish(self, uid, outcome):
        pass

    def record_submit(self, req):
        pass

    def flush(self):
        pass


def _capture(tmp_path, fn):
    """Run ``fn`` under the profiler; return its ``serve.*`` host spans."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("serve."):
                    spans.append(Span(e.name[len("serve."):], e.start_ns,
                                      e.end_ns, (plane.name, i),
                                      dict(e.stats)))
    return spans


def _inside(child, spans, names):
    return any(p.name in names and p.line == child.line
               and p.start <= child.start and child.end <= p.end
               for p in spans if p is not child)


def _check_nesting(spans):
    assert {s.name for s in spans} <= set(tracing.SPANS)
    for s in spans:
        if s.name in PARENTS:
            assert _inside(s, spans, PARENTS[s.name]), s


@pytest.fixture(scope="module")
def micro():
    cfg = get_config("gpt-micro")
    return cfg, get_family(cfg).init(jax.random.PRNGKey(0), cfg)


def _requests(n, new, vocab, rng):
    return [Request(uid=i, prompt=rng.integers(0, vocab, int(rng.integers(
        3, 14)), dtype=np.int32), max_new_tokens=new) for i in range(n)]


def test_engine_spans_nest_and_carry_uids(tmp_path, micro):
    cfg, params = micro
    eng = ContinuousBatchingEngine(cfg, params, capacity=3, max_len=48,
                                   prefill_bucket=8, k=4, pool="paged",
                                   journal=_Journal())
    reqs = _requests(7, 9, cfg.vocab_size, np.random.default_rng(0))
    eng.run(reqs[:2])  # compile outside the capture
    spans = _capture(tmp_path, lambda: eng.run(reqs[2:]))
    _check_nesting(spans)
    names = collections.Counter(s.name for s in spans)
    for name in ("step", "evict", "admit", "admit.alloc", "admit.prefill",
                 "admit.sync", "dispatch", "readback", "readback.wait",
                 "journal.flush"):
        assert names[name] > 0, name
    admitted = []
    for s in spans:
        if s.name == "admit":
            uids = ast.literal_eval(str(s.args["uids"]))
            assert int(s.args["n"]) == len(uids)
            admitted += uids
        if s.name == "admit.prefill":
            assert int(s.args["npad"]) >= int(s.args["n"]) >= 1
            assert int(s.args["bucket"]) % 8 == 0
        if s.name == "step":
            assert {"active", "waiting"} <= set(s.args)
    assert sorted(admitted) == [r.uid for r in reqs[2:]]


def test_no_span_per_token(tmp_path, micro):
    cfg, params = micro
    eng = ContinuousBatchingEngine(cfg, params, capacity=4, max_len=64,
                                   prefill_bucket=16, k=8, pool="paged")
    reqs = _requests(8, 40, cfg.vocab_size, np.random.default_rng(1))
    eng.run(reqs[:4])
    d0, t0 = eng.n_decode_dispatches, eng.n_tokens
    spans = _capture(tmp_path, lambda: eng.run(reqs[4:]))
    dispatches = eng.n_decode_dispatches - d0
    tokens = eng.n_tokens - t0
    names = collections.Counter(s.name for s in spans)
    assert names["dispatch"] == names["readback"] == dispatches
    assert names["readback.wait"] == dispatches
    # a block carries up to capacity x k tokens; its spans do not grow
    # with them
    assert tokens >= 4 * dispatches
    assert len(spans) < tokens / 2
    steps = [s for s in spans if s.name == "step"]
    for st in steps:
        inner = [s for s in spans if s is not st and s.line == st.line
                 and st.start <= s.start and s.end <= st.end]
        assert len(inner) <= 16


def test_upgrade_spans(tmp_path, micro):
    cfg, params = micro
    eng = ContinuousBatchingEngine(cfg, params, capacity=2, max_len=32,
                                   prefill_bucket=16, k=2)
    reqs = _requests(4, 8, cfg.vocab_size, np.random.default_rng(2))

    def go():
        UpgradeManager(eng, get_config("gpt-micro-big"),
                       speculate_after=False).start(background=False)
        eng.run(reqs)

    spans = _capture(tmp_path, go)
    _check_nesting(spans)
    by = {s.name: s for s in spans}
    for name in ("upgrade.grow", "upgrade.prewarm", "upgrade.swap"):
        assert name in by, name
    assert by["upgrade.grow"].end <= by["upgrade.prewarm"].start
    assert by["upgrade.prewarm"].end <= by["upgrade.swap"].start
    assert eng.upgrade.state == "swapped"


LOOPS = {
    "gpt-micro-paged": ("gpt-micro", "paged", "jnp", {}),
    "gpt-micro-paged-kernel": ("gpt-micro", "paged", "interpret", {}),
    "qwen-dense": ("qwen1.5-0.5b-smoke", "dense", "jnp", {}),
    "qwen-dense-kernel": ("qwen1.5-0.5b-smoke", "dense", "interpret", {}),
    "qwen-ring-paged": ("qwen1.5-0.5b-smoke", "paged", "jnp",
                        {"window": 16}),
    "mla-moe-paged": ("deepseek-v3-671b-smoke", "paged", "jnp", {}),
}
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = \S+ ([\w\-]+)\(")


@pytest.mark.parametrize("case", sorted(LOOPS))
def test_decode_loop_ops_are_scoped(case):
    arch, pool, kernel, extra = LOOPS[case]
    cfg = get_config(arch).replace(decode_kernel=kernel, **extra)
    params = get_family(cfg).init(jax.random.PRNGKey(0), cfg)
    eng = ContinuousBatchingEngine(cfg, params, capacity=2, max_len=48,
                                   prefill_bucket=16, k=2, pool=pool)
    assert eng.pool_kind == pool
    tokens, positions, remaining, eos_ids, done, _ = eng._state
    text = eng._loop.lower(eng.params, tokens, positions, remaining,
                           eos_ids, done,
                           eng._pools[0]).compile().as_text()
    assert text.startswith("HloModule jit_loop_fn")
    checked, unscoped_slices = collections.Counter(), 0
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        op = m.group(2)
        meta = re.search(r'op_name="([^"]*)"', line)
        path = meta.group(1).split("/") if meta else []
        scoped = any(p in tracing.SCOPES for p in path)
        if op in ("dot", "scatter", "custom-call"):
            assert scoped, line.strip()[:200]
            checked[op] += 1
        if op in ("dynamic-slice", "dynamic-update-slice") and path \
                and not scoped:
            unscoped_slices += 1
    assert checked["dot"] and checked["scatter"], checked
    # the layer scan's slicing of stacked weights and caches stays
    # outside every scope: it is state movement, not a layer
    assert unscoped_slices


def test_span_and_scope_names_come_from_tracing():
    """Every span and scope the program opens is one ``tracing`` lists,
    and only ``tracing`` calls the profiler and ``named_scope``."""
    used = {"span": set(), "scope": set()}
    for path in SRC.rglob("*.py"):
        text = path.read_text()
        if path.name != "tracing.py":
            assert "TraceAnnotation" not in text, path
            assert "named_scope" not in text, path
        for kind, name in re.findall(r'tracing\.(span|scope)\(\s*"([^"]+)"',
                                     text):
            used[kind].add(name)
    assert used["span"] == set(tracing.SPANS)
    assert used["scope"] == set(tracing.SCOPES)
