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
clock.  Host spans come from ``jax.profiler.TraceAnnotation`` names that
start with ``bench.``.
"""
from __future__ import annotations

import bisect
import collections
import contextlib
import glob
import os
import re
import shutil

from bench import stats

HOST_PREFIX = "bench."
_CONTAINERS = ("while", "conditional", "call")
_FINGERPRINT = re.compile(r"\(\d+\)$")


def module_base(name):
    """``jit_loop_fn(123)`` -> ``jit_loop_fn``."""
    return _FINGERPRINT.sub("", name)


def op_name(text):
    """``%copy.91 = bf16[...] copy(...)`` -> ``copy.91``."""
    return text.split(" = ", 1)[0].lstrip("%")


def is_container(name):
    return name.split(".", 1)[0] in _CONTAINERS


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

    modules[d]: sorted [(name, start, end)] with fingerprints removed;
    ops[d]: sorted [(op name, start, end, kind)] with kind "kernel",
    "container" or "op"; host: [(name, start, end)].
    """

    def __init__(self, modules, ops, host):
        self.modules = [sorted(map(tuple, m), key=lambda e: e[1])
                        for m in modules]
        self.ops = [sorted(map(tuple, o), key=lambda e: e[1]) for o in ops]
        self.host = [tuple(h) for h in host]
        self._starts = [[m[1] for m in mods] for mods in self.modules]

    # ------------------------------------------------------ serialization
    def to_json(self):
        return {"modules": self.modules, "ops": self.ops, "host": self.host}

    @classmethod
    def from_json(cls, d):
        return cls(d["modules"], d["ops"], d["host"])

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

    def module_of(self, dev, t):
        """Name of the program running on device ``dev`` at time ``t``."""
        i = bisect.bisect_right(self._starts[dev], t) - 1
        if i >= 0 and self.modules[dev][i][2] >= t:
            return self.modules[dev][i][0]
        return None

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
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    modules, ops, host = [], [], []
    devs = sorted((p for p in pd.planes
                   if p.name.startswith("/device:TPU:")),
                  key=lambda p: int(p.name.rsplit(":", 1)[1]))
    for plane in devs:
        mods, dops = [], []
        for line in plane.lines:
            if line.name == "XLA Modules":
                mods = [(module_base(e.name), e.start_ns, e.end_ns)
                        for e in line.events]
            elif line.name == "XLA Ops":
                for e in line.events:
                    name = op_name(e.name)
                    kind = ("container" if is_container(name) else
                            "kernel" if is_kernel(e.name) else "op")
                    dops.append((name, e.start_ns, e.end_ns, kind))
        modules.append(mods)
        ops.append(dops)
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(HOST_PREFIX):
                    host.append((e.name, e.start_ns, e.end_ns))
    return Trace(modules, ops, host)


@contextlib.contextmanager
def capture(log_dir):
    """Profile the block into ``log_dir`` (emptied first) and yield a
    callable that returns the ``.xplane.pb`` path once the block ends."""
    import jax

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
