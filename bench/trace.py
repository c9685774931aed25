"""Profiler trace of a run, reduced to what the per-layer readers need.

``capture(dir)`` wraps the measured window in ``jax.profiler`` with the
Python tracer off.  ``load(path)`` reads the ``.xplane.pb`` with
``jax.profiler.ProfileData`` into a ``Trace`` of plain tuples; ``Trace``
is also what the recorded test trace deserializes into, so every number
below is computed the same way on the chip and in the tests.

On a TPU the device plane ``/device:TPU:<i>`` has a line ``XLA Modules``
(one event per program run, named ``jit_<fn>(<fingerprint>)``) and a
line ``XLA Ops`` (one event per HLO op, named by its HLO text
``%<name> = <shape> <opcode>(...)``).  Host and device events share one
clock.  Host spans come from ``jax.profiler.TraceAnnotation`` names:
the harness's own start with ``bench.``, the program's with ``serve.``;
they are kept apart.

The v5e's ``XLA Ops`` events carry no op metadata, so each op's scope
comes from the compiled text of its program, whose instruction names are
the trace's: ``Programs`` records the arguments of every jitted program
an object calls, and after the window (traced runs only) the compiled
text of each gives the map from (program, instruction) to the op's
``op_name`` path (its ``jax.named_scope`` names, ``/``-joined).
"""
from __future__ import annotations

import bisect
import collections
import contextlib
import functools
import glob
import os
import re
import shutil

import jax
import numpy as np

from bench import stats

HOST_PREFIX = "bench."
PROGRAM_PREFIX = "serve."
UNSCOPED = "unscoped"  # an op whose metadata names no op_name
UNPLACED = "unplaced"  # an op the scope map does not hold
_CONTAINERS = ("while", "conditional", "call")
_FINGERPRINT = re.compile(r"\(\d+\)$")
_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([^\s=]+) = (.*)$", re.M)
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')
_HEAD = re.compile(r"(%\S+ = .*? [\w-]+)\(")


def module_base(name):
    """``jit_loop_fn(123)`` -> ``jit_loop_fn``."""
    return _FINGERPRINT.sub("", name)


def op_name(text):
    """``%copy.91 = bf16[...] copy(...)`` -> ``copy.91``."""
    return text.split(" = ", 1)[0].lstrip("%")


def is_container(name):
    return name.split(".", 1)[0] in _CONTAINERS


def op_head(text):
    """``%copy.2 = bf16[8]{0} copy(...)`` -> ``%copy.2 = bf16[8]{0} copy``:
    name, shape with its layout, and opcode, as both a trace's op and its
    program's compiled text print them."""
    m = _HEAD.match(text)
    return m.group(1) if m else text


def parse_program(text):
    """(module name, {instruction: op_name path, "" where none},
    {instruction: head}) of a compiled HLO text."""
    module = _MODULE.search(text).group(1)
    paths, heads = {}, {}
    for name, rest in _INSTRUCTION.findall(text):
        m = _OP_NAME.search(rest)
        paths[name] = m.group(1) if m else ""
        heads[name] = op_head(f"%{name} = {rest}")
    return module, paths, heads


def is_kernel(text):
    """A Pallas (Mosaic) kernel: a custom call whose target is the TPU
    custom call; an HLO text without a target names the kernel itself."""
    if " custom-call(" not in text:
        return False
    if "custom_call_target=" in text:
        return 'custom_call_target="tpu_custom_call"' in text
    return not op_name(text).startswith("custom-call")


class Trace:
    """Device modules and ops (per device) and host spans, in ns.

    modules[d]: sorted [(name, start, end)] with fingerprints removed,
    and module_ids[d] the name of each with its fingerprint (one per
    executable); ops[d]: sorted [(op name, start, end, kind)] with kind
    "kernel", "container" or "op"; host: [(name, start, end)] of the
    harness's ``bench.`` spans; spans: the same of the program's
    ``serve.`` spans; heads: {module id: {op name: ``op_head``}} as the
    trace prints them.

    The scope map: ``add_program_text`` gives each executable's
    instructions with their heads; each module id takes the executable of
    its program whose heads agree with every op of the id in the trace
    (where several do, their paths where they agree; where none does,
    none: the id's ops stay unplaced), and scopes: {module id: {op name:
    op_name path, or None}}.
    """

    def __init__(self, modules, ops, host, spans=(), heads=None,
                 executables=None):
        self.modules, self.module_ids = [], []
        for mods in modules:
            mods = sorted(map(tuple, mods), key=lambda e: e[1])
            self.modules.append([m[:3] for m in mods])
            self.module_ids.append([m[3] if len(m) > 3 else m[0]
                                    for m in mods])
        self.ops = [sorted(map(tuple, o), key=lambda e: e[1]) for o in ops]
        self.host = [tuple(h) for h in host]
        self.spans = [tuple(h) for h in spans]
        self.heads = dict(heads or {})
        self.executables = {}  # program -> [(paths, heads)]
        self.scopes = {}
        self._starts = [[m[1] for m in mods] for mods in self.modules]
        for module, tables in (executables or {}).items():
            for paths, hds in tables:
                self._add(module, paths, hds)

    # ------------------------------------------------------ serialization
    def to_json(self):
        modules = [[(*m, i) for m, i in zip(mods, ids)]
                   for mods, ids in zip(self.modules, self.module_ids)]
        return {"modules": modules, "ops": self.ops, "host": self.host,
                "spans": self.spans, "heads": self.heads,
                "executables": self.executables}

    @classmethod
    def from_json(cls, d):
        return cls(d["modules"], d["ops"], d["host"], d.get("spans", ()),
                   d.get("heads"), d.get("executables"))

    # -------------------------------------------------------- scope map
    def program_names(self):
        return {name for mods in self.modules for name, _, _ in mods}

    def add_program_text(self, text):
        """Put the instructions of one compiled program in the scope
        map."""
        self._add(*parse_program(text))

    def _add(self, module, paths, heads):
        self.executables.setdefault(module, []).append((paths, heads))
        for mid in {m for ids in self.module_ids for m in ids
                    if module_base(m) == module}:
            self.scopes[mid] = self._place(mid)

    def _place(self, mid):
        exes = self.executables.get(module_base(mid), [])
        seen = self.heads.get(mid, {})
        fits = [paths for paths, heads in exes
                if all(heads.get(n) == h for n, h in seen.items())]
        table = {}
        for paths in fits:
            for n, path in paths.items():
                table[n] = None if table.get(n, path) != path else path
        return table

    @staticmethod
    def _dev(per_device, dev):
        return per_device[dev] if dev < len(per_device) else []

    # ----------------------------------------------------------- windows
    def window(self, name=HOST_PREFIX + "window"):
        """(start, end) of the host span ``name``."""
        for n, s, e in self.host:
            if n == name:
                return s, e
        raise KeyError(name)

    def _module_index(self, dev, t):
        i = bisect.bisect_right(self._starts[dev], t) - 1
        return i if i >= 0 and self.modules[dev][i][2] >= t else None

    def module_of(self, dev, t):
        """Name of the program running on device ``dev`` at time ``t``."""
        i = self._module_index(dev, t)
        return None if i is None else self.modules[dev][i][0]

    def _path(self, dev, name, t):
        """The op_name path of op ``name`` run at time ``t`` ("" where it
        has none; None where the scope map does not hold it)."""
        i = self._module_index(dev, t)
        return None if i is None else \
            self.scopes.get(self.module_ids[dev][i], {}).get(name)

    # --------------------------------------------------------- reductions
    def busy_ns(self, lo, hi):
        """Mean over devices of the union of op intervals in [lo, hi]."""
        per = [stats.union_length([(s, e) for _, s, e, _ in ops], lo, hi)
               for ops in self.ops]
        return sum(per) / max(len(per), 1)

    def program_ns(self, prefix, lo, hi, dev=0):
        """(device ns, runs) of programs whose name starts with
        ``prefix`` and that start inside [lo, hi]."""
        total, runs = 0, 0
        for name, s, e in self._dev(self.modules, dev):
            if name.startswith(prefix) and lo <= s <= hi:
                total += e - s
                runs += 1
        return total, runs

    def kernel_ns(self, program_prefix, lo, hi, dev=0):
        """(device ns, calls) of the kernels run inside programs whose
        name starts with ``program_prefix``, started inside [lo, hi]."""
        total, calls = 0, 0
        for name, s, e, kind in self._dev(self.ops, dev):
            if kind != "kernel" or not lo <= s <= hi:
                continue
            mod = self.module_of(dev, s)
            if mod is not None and mod.startswith(program_prefix):
                total += e - s
                calls += 1
        return total, calls

    def top_ops(self, lo, hi, n=10, dev=0):
        """The ``n`` device ops (``program/op``) that took most time."""
        acc = collections.Counter()
        for name, s, e, kind in self._dev(self.ops, dev):
            if kind == "container" or not lo <= s <= hi:
                continue
            acc[f"{self.module_of(dev, s)}/{name}"] += e - s
        return [[k, v / 1e9] for k, v in acc.most_common(n)]

    def scope_ns(self, program_prefix, lo, hi, dev=0):
        """{op_name path: device ns} of the ops run inside programs whose
        name starts with ``program_prefix``, started inside [lo, hi];
        ``UNSCOPED`` holds ops with no path, ``UNPLACED`` those the map
        does not hold.  None where the map holds none of those programs.
        """
        if not any(p.startswith(program_prefix) for p in self.scopes):
            return None
        acc = collections.Counter()
        for name, s, e, kind in self._dev(self.ops, dev):
            if kind == "container" or not lo <= s <= hi:
                continue
            mod = self.module_of(dev, s)
            if mod is None or not mod.startswith(program_prefix):
                continue
            path = self._path(dev, name, s)
            acc[UNPLACED if path is None else path or UNSCOPED] += e - s
        return dict(acc)

    def unplaced_ops(self, lo, hi, n=20, dev=0):
        """{program: [up to ``n`` names, count]} of the distinct ops
        started inside [lo, hi] that the scope map does not hold."""
        miss = collections.defaultdict(set)
        for name, s, _, kind in self._dev(self.ops, dev):
            if kind != "container" and lo <= s <= hi \
                    and self._path(dev, name, s) is None:
                miss[self.module_of(dev, s)].add(name)
        return {p: [sorted(m)[:n], len(m)] for p, m in sorted(
            miss.items(), key=lambda kv: str(kv[0]))}

    def idle_by_host(self, lo, hi, n=10, dev=0):
        """Idle device time in [lo, hi], summed by what the host was
        doing: the ``bench.`` span overlapping each gap most."""
        idle = stats.gaps([(s, e) for _, s, e, _ in self._dev(self.ops, dev)],
                          lo, hi)
        spans = [h for h in self.host
                 if h[0] != HOST_PREFIX + "window"]
        acc = collections.Counter()
        for gs, ge in idle:
            best, label = 0, "host:other"
            for name, s, e in spans:
                ov = min(e, ge) - max(s, gs)
                if ov > best:
                    best, label = ov, name
            acc[label] += ge - gs
        return [[k, v / 1e9] for k, v in acc.most_common(n)]


def load(path):
    """``Trace`` from an ``.xplane.pb`` written by ``jax.profiler``."""
    pd = jax.profiler.ProfileData.from_file(path)
    modules, ops, host, spans, heads = [], [], [], [], {}
    devs = sorted((p for p in pd.planes
                   if p.name.startswith("/device:TPU:")),
                  key=lambda p: int(p.name.rsplit(":", 1)[1]))
    for plane in devs:
        lines = {line.name: line for line in plane.lines}
        mods = sorted(((module_base(e.name), e.start_ns, e.end_ns, e.name)
                       for e in (lines["XLA Modules"].events
                                 if "XLA Modules" in lines else ())),
                      key=lambda m: m[1])
        starts = [m[1] for m in mods]
        dops, i = [], -1
        for e in (lines["XLA Ops"].events if "XLA Ops" in lines else ()):
            name = op_name(e.name)
            kind = ("container" if is_container(name) else
                    "kernel" if is_kernel(e.name) else "op")
            t = e.start_ns
            dops.append((name, t, e.end_ns, kind))
            # the program run holding the op (ops come in time order)
            while i + 1 < len(starts) and starts[i + 1] <= t:
                i += 1
            if i >= 0 and starts[i] > t:
                i = bisect.bisect_right(starts, t) - 1
            if kind != "container" and i >= 0 and mods[i][2] >= t:
                seen = heads.setdefault(mods[i][3], {})
                if name not in seen:
                    seen[name] = op_head(e.name)
        modules.append(mods)
        ops.append(dops)
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(HOST_PREFIX):
                    host.append((e.name, e.start_ns, e.end_ns))
                elif e.name.startswith(PROGRAM_PREFIX):
                    spans.append((e.name, e.start_ns, e.end_ns))
    return Trace(modules, ops, host, spans, heads)


class Programs:
    """The jitted programs an object holds, with abstract arguments of
    each distinct call, recorded as they are called (``watch``), so that
    their compiled text can be had again after the window."""

    def __init__(self):
        self.calls = {}  # (program, argument signature) -> (fn, args, kw)

    def watch(self, obj):
        """Record the calls of every jitted function among ``obj``'s
        attributes."""
        for name, fn in list(vars(obj).items()):
            if callable(fn) and hasattr(fn, "lower"):
                setattr(obj, name, self._wrap(fn))

    def _wrap(self, fn):
        @functools.wraps(fn)
        def call(*args, **kw):
            leaves, tree = jax.tree_util.tree_flatten((args, kw))
            key = (id(fn), tree, tuple(map(_signature, leaves)))
            if key not in self.calls:
                a, k = jax.tree_util.tree_unflatten(
                    tree, [_abstract(x) for x in leaves])
                self.calls[key] = (fn, a, k)
            return fn(*args, **kw)

        return call

    def texts(self, names):
        """Compiled HLO text of each recorded call whose program is named
        in ``names``, compiled again from its abstract arguments; JAX's
        persistent cache, where on, serves each."""
        for fn, args, kw in self.calls.values():
            if f"jit_{fn.__name__}" in names:
                yield fn.lower(*args, **kw).compile().as_text()


def _signature(x):
    """What of an argument the compiled program depends on: an array's
    abstract value, and any other leaf's value (a static argument's
    selects its own program)."""
    if isinstance(x, jax.Array):
        return (x.shape, x.dtype, x.weak_type, x.sharding)
    if isinstance(x, np.ndarray):
        return (x.shape, x.dtype)
    try:
        hash(x)
    except TypeError:
        return type(x)
    return (type(x), x)


def _abstract(x):
    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding,
                                    weak_type=x.weak_type)
    if isinstance(x, np.ndarray):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    return x


@contextlib.contextmanager
def capture(log_dir):
    """Profile the block into ``log_dir`` (emptied first) and yield a
    callable that returns the ``.xplane.pb`` path once the block ends."""
    shutil.rmtree(log_dir, ignore_errors=True)
    os.makedirs(log_dir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    found = []
    try:
        yield lambda: found[0]
    finally:
        jax.profiler.stop_trace()
        found.extend(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                               recursive=True))
