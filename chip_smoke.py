#!/usr/bin/env python3
"""Chip smoke: Faaslet serving of qwen1.5-0.5b on one TPU chip.

    python3 chip_smoke.py [--seed N]

Drives the system's main path once, at qwen1.5-0.5b's published widths
with random weights made from ``--seed``, in one process on one chip:

  (a) refuses to run unless JAX's first device is a TPU and ``backend="auto"``
      resolves to the compiled Pallas kernels;
  (b) checks the Pallas kernels against the ``xla`` path at the model's
      attention shapes, and the device int8 state encode against the host
      codec at the size of the ``serve/stats`` vector;
  (c) serves 8 requests through Faaslets (``run_faasm_fanout`` with the int8
      state push) and checks each returned token against a direct jitted
      forward pass of the same prompt;
  (d) runs prefill and 4 decode steps at batch 2 (``serve.generate``) and
      checks the last step's logits against the direct forward pass.

A failed check raises, and the script exits nonzero without printing a
result.  The lines before the last are observations of this one smoke run
(compile and wall seconds and peak device memory per phase), not benchmark
metrics.  The last line is the JSON result.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402
import numpy as np                                           # noqa: E402

from repro.configs import get_config                         # noqa: E402
from repro.kernels.common import resolve_backend             # noqa: E402
from repro.kernels.decode_attention import decode_attention  # noqa: E402
from repro.kernels.flash_attention import flash_attention    # noqa: E402
from repro.kernels.state_push import hostcodec               # noqa: E402
from repro.kernels.state_push import ops as state_push       # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import generate, run_faasm_fanout    # noqa: E402
from repro.models import ExecConfig, build_model             # noqa: E402

ARCH = "qwen1.5-0.5b"
N_REQUESTS = 8
PROMPT_LEN = 16
# bf16 outputs of two implementations that accumulate in f32 in different
# orders: a few bf16 ulps apart (one ulp is 2^-8 relative)
ATTN_ATOL = ATTN_RTOL = 2e-2
# decode through the KV cache vs a full forward pass over the same tokens:
# 24 bf16 layers, compared as relative L2 error of the logits
DECODE_LOGITS_RTOL = 5e-2
# device vs host int8 encode: the chip's f32 division may differ from
# numpy's by an ulp, so a scale may move by an ulp and a code sitting on a
# rounding boundary by one step
SCALE_RTOL = 1e-6
MAX_CODE_MISMATCH_SHARE = 1e-3

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _fail(msg: str):
    raise SystemExit(f"chip_smoke: {msg}")


def _check(ok, msg: str) -> None:
    if not ok:
        _fail(msg)


class Phases:
    """Per-phase compile seconds (JAX's backend-compile events, persistent
    cache reads included), wall seconds and peak device memory."""

    def __init__(self, device):
        self.device = device
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.compile_s += duration

    @contextlib.contextmanager
    def phase(self, name: str):
        c0, t0 = self.compile_s, time.perf_counter()
        yield
        wall = time.perf_counter() - t0
        peak = self.device.memory_stats().get("peak_bytes_in_use")
        print(f"smoke observation, phase {name}: compile_s="
              f"{self.compile_s - c0!r} wall_s={wall!r} "
              f"peak_bytes_in_use={peak}", flush=True)


def require_chip():
    """Phase (a): a TPU, and the compiled Pallas kernels behind ``auto``."""
    dev = jax.devices()[0]
    _check(dev.platform == "tpu",
           f"needs a TPU; JAX's first device is {dev.platform!r}")
    _check(resolve_backend("auto") == "pallas",
           f"backend 'auto' resolves to {resolve_backend('auto')!r}, "
           f"not 'pallas'")
    return dev


def _max_excess(got, want, atol, rtol) -> float:
    """max(|got - want| - (atol + rtol·|want|)); <= 0 means within bounds."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want) - (atol + rtol * np.abs(want))))


def check_kernels(cfg, seed: int) -> None:
    """Phase (b): ``auto`` (the Pallas kernels) against ``xla`` at the
    model's attention shapes, and the device int8 encode against the
    host codec."""
    H, K, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    normal = lambda k, s: jax.random.normal(k, s, jnp.bfloat16)

    S = 512
    q, k, v = (normal(ks[0], (1, S, H, D)), normal(ks[1], (1, S, K, D)),
               normal(ks[2], (1, S, K, D)))
    got, want = (jax.jit(lambda q, k, v, b=b: flash_attention(
        q, k, v, backend=b))(q, k, v) for b in (None, "xla"))
    _check(got.shape == (1, S, H, D) and bool(jnp.isfinite(got).all()),
           f"flash_attention: bad output {got.shape}")
    ex = _max_excess(got, want, ATTN_ATOL, ATTN_RTOL)
    print(f"smoke observation, flash_attention S={S}: max|pallas-xla|="
          f"{float(jnp.abs(got.astype(jnp.float32) - want).max())!r}")
    _check(ex <= 0, f"flash_attention differs from xla beyond tolerance "
                    f"(excess {ex})")

    B, S = 8, 2048
    q, k, v = (normal(ks[3], (B, H, D)), normal(ks[4], (B, S, K, D)),
               normal(ks[5], (B, S, K, D)))
    lengths = jax.random.randint(ks[6], (B,), 1, S + 1)
    got, want = (jax.jit(lambda q, k, v, n, b=b: decode_attention(
        q, k, v, n, backend=b))(q, k, v, lengths) for b in (None, "xla"))
    _check(got.shape == (B, H, D) and bool(jnp.isfinite(got).all()),
           f"decode_attention: bad output {got.shape}")
    ex = _max_excess(got, want, ATTN_ATOL, ATTN_RTOL)
    print(f"smoke observation, decode_attention B={B} S={S}: "
          f"max|pallas-xla|="
          f"{float(jnp.abs(got.astype(jnp.float32) - want).max())!r}")
    _check(ex <= 0, f"decode_attention differs from xla beyond tolerance "
                    f"(excess {ex})")

    # the serve/stats vector: one f32 per token id
    n = cfg.vocab_size
    eff = jax.random.normal(ks[7], (n,), jnp.float32)
    base = jnp.zeros((n,), jnp.float32).at[::3].set(0.5)
    qd, sd, numel, _ = state_push.encode_quant(eff, base)
    qh, sh, _, _ = hostcodec.encode_quant(np.asarray(eff), np.asarray(base))
    _check(numel == n and qd.shape == qh.shape and sd.shape == sh.shape,
           f"int8 encode: shapes {qd.shape}/{sd.shape} vs host "
           f"{qh.shape}/{sh.shape}")
    code_diff = np.abs(qd.astype(np.int32) - qh.astype(np.int32)).reshape(-1)
    scale_rel = np.abs(sd - sh) / sh
    print(f"smoke observation, int8 encode numel={n}: codes differing="
          f"{int((code_diff[:n] != 0).sum())} max_scale_rel_diff="
          f"{float(scale_rel.max())!r}")
    _check(float(scale_rel.max()) <= SCALE_RTOL,
           f"int8 encode: scales differ from the host codec by "
           f"{float(scale_rel.max())} relative")
    _check(int(code_diff.max()) <= 1
           and (code_diff[:n] != 0).mean() <= MAX_CODE_MISMATCH_SHARE,
           "int8 encode: codes differ from the host codec beyond one step "
           "or too often")
    _check(not code_diff[n:].any() and not qd.reshape(-1)[n:].any(),
           "int8 encode: the pad region holds nonzero codes")


def serve_through_faaslets(model, params) -> None:
    """Phase (c): Faaslet-served requests, each token checked against the
    direct forward pass on the same chip."""
    cfg = model.cfg
    r = run_faasm_fanout(model, params, cfg.vocab_size, N_REQUESTS,
                         prompt_len=PROMPT_LEN, state_wire="int8")
    _check(r["codes"] == [0] * N_REQUESTS, f"return codes {r['codes']}")
    fwd = jax.jit(lambda p, t: model.logits(p, t))
    want = [int(jnp.argmax(fwd(params, jnp.asarray(p)[None])[0, -1]))
            for p in r["prompts"]]
    print(f"smoke observation, faaslet serving: tokens={r['tokens']} "
          f"wall_s={r['wall_s']!r} p50_ms={r['p50_ms']!r} "
          f"state_push_mb={r['state_push_mb']!r}")
    _check(r["tokens"] == want,
           f"Faaslet tokens {r['tokens']} != direct forward {want}")
    # int8 frames carry about a quarter of the exact wire's f32 bytes
    exact_bytes = N_REQUESTS * 4 * cfg.vocab_size
    _check(0 < r["state_push_mb"] * 1e6 <= 0.3 * exact_bytes,
           f"serve/stats pushes moved {r['state_push_mb']} MB; int8 frames "
           f"would move at most {0.3 * exact_bytes / 1e6} MB")


def generate_and_check(model, params, seed: int) -> None:
    """Phase (d): prefill + 4 decode steps at batch 2 through the KV cache,
    the last step's logits checked against a full forward pass."""
    cfg = model.cfg
    new_tokens = 5
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, PROMPT_LEN)),
                         jnp.int32)
    gen, logits = generate(model, params, tokens, new_tokens)
    _check(gen.shape == (2, new_tokens)
           and bool(((gen >= 0) & (gen < cfg.vocab_size)).all()),
           f"generate: bad ids {gen}")
    _check(bool(jnp.isfinite(logits).all()), "generate: non-finite logits")
    seq = jnp.concatenate([tokens, jnp.asarray(gen[:, :-1])], axis=1)
    ref = jax.jit(lambda p, t: model.logits(p, t))(params, seq)[:, -1]
    rel = float(jnp.linalg.norm(logits - ref) / jnp.linalg.norm(ref))
    print(f"smoke observation, generate: ids={gen.tolist()} "
          f"decode_vs_forward_rel_l2={rel!r}")
    _check(rel <= DECODE_LOGITS_RTOL,
           f"decode logits differ from the forward pass by {rel} (relative "
           f"L2)")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and inputs")
    args = ap.parse_args()

    enable_compile_cache()
    dev = require_chip()
    print(f"smoke observation: device_kind={dev.device_kind!r} "
          f"count={len(jax.devices())}", flush=True)
    phases = Phases(dev)
    cfg = get_config(ARCH)
    with phases.phase("b kernels"):
        check_kernels(cfg, args.seed)
    with phases.phase("c faaslet serving"):
        model = build_model(cfg, ExecConfig(backend="auto", loss_chunk=0))
        # one program, not one compile per init op
        params = jax.jit(model.init)(jax.random.PRNGKey(args.seed))
        serve_through_faaslets(model, params)
    with phases.phase("d prefill+decode"):
        generate_and_check(model, params, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
