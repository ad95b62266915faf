"""The float32 references agree with the program's forward pass.

At a small size on the CPU, with the program run in float32 on its plain
``xla`` path, the reference's last-position logits must match the
program's: two independent implementations of one architecture."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import correct, manifest, weights  # noqa: E402

CASES = [("qwen1.5-0.5b", "bench/configs/qwen1.5-0.5b.json")]


def small(arch, path, dtype):
    from repro.configs import smoke_config
    mc = smoke_config(arch).with_overrides(vocab_size=512, dtype=dtype,
                                           param_dtype=dtype)
    cfg = json.loads((manifest.ROOT / path).read_text())
    ref = correct.reference(cfg)
    ref_cfg = dict(cfg, **{k: getattr(mc, a)
                           for k, a in ref.REGISTRY_KEYS.items()})
    return mc, ref, ref_cfg


@pytest.mark.parametrize("arch,path", CASES)
def test_reference_matches_program_in_float32(arch, path):
    from repro.models import ExecConfig, build_model
    mc, ref, ref_cfg = small(arch, path, "float32")
    model = build_model(mc, ExecConfig(backend="xla", loss_chunk=0))
    params = weights.make(model.init, 7)
    tokens = np.random.default_rng(0).integers(0, 512, (2, 40)).astype(np.int32)
    prog = np.asarray(model.logits(params, tokens))[:, -1]
    want = ref.last_logits(params, ref_cfg, tokens)
    scale = np.abs(want).max()
    assert np.abs(prog - want).max() <= 1e-4 * scale


@pytest.mark.parametrize("arch,path", CASES)
def test_float8_control_is_coarser_than_bfloat16(arch, path):
    """The control (float8 operands) departs from the float32 reference by
    far more than the bfloat16 program does, on the same weights."""
    from repro.models import ExecConfig, build_model
    mc, ref, ref_cfg = small(arch, path, "bfloat16")
    model = build_model(mc, ExecConfig(backend="xla", loss_chunk=0))
    params = weights.make(model.init, 3)
    tokens = np.random.default_rng(1).integers(0, 512, (8, 32)).astype(np.int32)
    want = ref.last_logits(params, ref_cfg, tokens)
    prog = np.asarray(model.logits(params, tokens))[:, -1]
    ctl = ref.last_logits(params, ref_cfg, tokens, mode="fp8")
    err = lambda x: np.abs(x - want).max()
    assert err(ctl) > 3 * err(prog)


def test_weights_repeat_for_a_seed_and_differ_across_seeds():
    from repro.configs import smoke_config
    from repro.models import ExecConfig, build_model
    model = build_model(smoke_config("qwen1.5-0.5b"), ExecConfig(backend="xla"))
    a = weights.make(model.init, 2 ** 40 + 5)
    b = weights.make(model.init, 2 ** 40 + 5)
    c = weights.make(model.init, 6)
    la, lb, lc = (np.asarray(x["layers"]["attn"]["bq"], np.float32)
                  for x in (a, b, c))
    np.testing.assert_array_equal(la, lb)
    assert not np.array_equal(la, lc) and np.abs(la).max() > 0
