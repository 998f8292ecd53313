"""The plain reference against the program's own forward pass, at a
small size on the CPU, on the benchmark's seeded weights: the same
function, so the served-token check compares like with like."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "perfbench"))
sys.path.insert(0, str(ROOT / "src"))

BASE = {"hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4,
        "num_hidden_layers": 3, "vocab_size": 200,
        "max_position_embeddings": 4096, "rope_theta": 10000.0,
        "source": "test"}
MHA = dict(BASE, num_key_value_heads=4, layer_norm_eps=1e-5,
           tie_word_embeddings=False)
GQA = dict(BASE, num_key_value_heads=2, head_dim=32, qk_norm=True,
           rms_norm_eps=1e-6, tie_word_embeddings=True,
           rope_theta=1000000.0)


@pytest.mark.parametrize("cfg", [MHA, GQA], ids=["mha", "gqa_qknorm_tied"])
def test_reference_matches_the_program_forward(cfg):
    import jax
    import jax.numpy as jnp
    from harness import reference, weights
    from harness.system import arch_from_config
    from repro.core.views import SINGLE
    from repro.models.cache import TrainBackend
    from repro.models.model import build_model

    arch = arch_from_config("t", cfg)
    model = build_model(arch, dtype=jnp.float32)
    params = weights.make_params(model.param_specs(), None, 2**33 + 7)
    toks = np.random.default_rng(0).integers(0, cfg["vocab_size"], 40)
    with jax.default_matmul_precision("highest"):
        prog, _, _ = model.forward(params, SINGLE, mode="train",
                                   tokens=jnp.asarray(toks)[None],
                                   backend=TrainBackend())
    prog = np.asarray(prog[0])
    ref = np.asarray(reference.logits_at(weights.named(params), cfg, toks,
                                         0, 40, lo=64))
    assert ref.shape == prog.shape
    scale = np.abs(ref).max()
    assert np.abs(ref - prog).max() <= 1e-4 * scale
    assert scale > 0.5          # logits are not degenerate


def test_seed_drives_the_weights():
    import jax.numpy as jnp
    from harness import weights
    from harness.system import arch_from_config
    from repro.models.model import build_model
    model = build_model(arch_from_config("t", MHA), dtype=jnp.float32)
    a = weights.make_params(model.param_specs(), None, 5)
    b = weights.make_params(model.param_specs(), None, 5 + 2**32)
    c = weights.make_params(model.param_specs(), None, 5)
    ta, tb, tc = (np.asarray(x["embed"]["tok"]) for x in (a, b, c))
    assert np.array_equal(ta, tc) and not np.array_equal(ta, tb)


def test_fp8_control_departs_from_the_reference():
    import jax.numpy as jnp
    from harness import reference, weights
    from harness.system import arch_from_config
    from repro.models.model import build_model
    model = build_model(arch_from_config("t", GQA), dtype=jnp.float32)
    w = weights.named(weights.make_params(model.param_specs(), None, 3))
    toks = np.arange(30) % GQA["vocab_size"]
    ref = np.asarray(reference.logits_at(w, GQA, toks, 0, 30, lo=64))
    ctl = np.asarray(reference.logits_at(w, GQA, toks, 0, 30, quant="fp8",
                                         lo=64))
    rel = np.abs(ctl - ref).max() / np.abs(ref).max()
    assert 1e-3 < rel < 0.5
