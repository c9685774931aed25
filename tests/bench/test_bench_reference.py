"""The plain reference agrees with the program at small sizes: the
decoder forward with the configuration's features."""
import jax
import numpy as np
import pytest

import bench_tiny
from bench import weights
from bench.reference import transformer as ref
from repro.configs.base import get_config
from repro.models import get_family


def _params(cfg, seed):
    fam = get_family(cfg)
    shapes = jax.eval_shape(lambda: fam.init(jax.random.PRNGKey(0), cfg))
    return weights.make_params(shapes, weights.seed_key(seed))


@pytest.mark.parametrize("arch, conf", [
    ("qwen1.5-0.5b-smoke", bench_tiny.QWEN),
])
def test_reference_forward_matches_the_program(arch, conf):
    cfg = get_config(arch)
    spec = ref.describe(conf)
    params = _params(cfg, 5)
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, 40,
                                             dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(get_family(cfg).forward(
            params, {"tokens": toks[None]}, cfg)[0][0])
    got = np.asarray(ref.logits_at(spec, params, toks, np.arange(40)))
    np.testing.assert_allclose(got, want, atol=2e-4 * np.abs(want).max())


def test_reference_describes_only_known_models():
    with pytest.raises(ValueError):
        ref.describe({"model_type": "gpt2"})
