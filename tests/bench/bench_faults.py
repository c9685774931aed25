"""Faults planted under the harness for the CPU tests: each must make a
run's ``correct`` come out false."""
import jax

from repro.serve.engine import ContinuousBatchingEngine


def alter_tokens(monkeypatch):
    """A token altered where it is produced: the first step of every
    macro block reads back one id higher than the device computed."""
    real = ContinuousBatchingEngine._process

    def process(self, item):
        block, *rest = item
        block = jax.device_get(block).copy()
        block[0] = (block[0] + 1) % self.cfg.vocab_size
        return real(self, (block, *rest))

    monkeypatch.setattr(ContinuousBatchingEngine, "_process", process)
