"""Smoke run of the serving path on a TPU: qwen3-4b at its published
widths, seeded random bf16 weights, served through ``BatchServer``.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # tensor-parallel over four chips
                                     # against the same requests on one

One chip: builds the server, serves a batch of seeded greedy requests
twice (cold, then warm), and checks the last decode step's logits
against a fresh prefill of prompt + generated tokens. Four chips: serves
the same requests on a one-device mesh and on a model-axis-4 mesh in
this process, and checks that the greedy tokens and the last logits
agree. Earlier lines report sizes, times and peak device memory; the
last line is the JSON result. Without a TPU, or on a failed check, the
script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ARCH = "qwen3-4b"
BATCH = 8
GEN = 32
MAX_LEN = 1024
PROMPT_LENS = (16, 257)       # prompt lengths drawn from [16, 256]
SEED = 0
# bf16 agreement bound between two programs computing the same logits:
# max |a - b| <= LOGIT_RTOL * max |b|. bf16 keeps 8 significant bits
# (relative step 2**-8), and two programs round at different places;
# over 36 layers that adds up to about 2**-6 of the largest logit (a
# 36-layer bf16 model at width 256, on the CPU). A wrong cache entry,
# position or weight shows as an error of the logits' own size.
LOGIT_RTOL = 2.0 ** -4
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    """Counts backend compilations (or persistent-cache loads) and their
    seconds while active."""

    def __init__(self):
        self.count = 0
        self.secs = 0.0

    def _on(self, event: str, secs: float, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.count += 1
            self.secs += secs

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


def make_requests(vocab: int, batch: int = BATCH, gen: int = GEN,
                  lens: tuple[int, int] = PROMPT_LENS, seed: int = SEED):
    """Seeded greedy requests with prompts of mixed length."""
    from repro.launch.serve import Request
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(0, vocab, n).astype(np.int32),
                    max_new=gen)
            for i, n in enumerate(rng.integers(*lens, batch))]


def tokens_of(stats: dict) -> np.ndarray:
    return np.array([stats["outputs"][i] for i in sorted(stats["outputs"])],
                    np.int32)


def compare_logits(got, want, rtol: float = LOGIT_RTOL) -> list[str]:
    """Failures (empty if none) of ``got`` against ``want`` (B, V):
    every value finite, greedy argmax equal per row, max-abs difference
    within ``rtol`` of the largest reference magnitude."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    fails = []
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        fails.append("non-finite logits")
        return fails
    diff = float(np.abs(got - want).max())
    bound = rtol * float(np.abs(want).max())
    rows = np.flatnonzero(got.argmax(-1) != want.argmax(-1))
    print(f"  logits max|diff| {diff:.6g} (bound {bound:.6g}), "
          f"argmax differs in rows {rows.tolist()}")
    if diff > bound:
        fails.append(f"max|diff| {diff:.6g} > {bound:.6g}")
    if rows.size:
        fails.append(f"greedy argmax differs in rows {rows.tolist()}")
    return fails


def param_summary(params) -> tuple[int, set[str]]:
    import jax
    leaves = jax.tree.leaves(params)
    return sum(x.size for x in leaves), {str(x.dtype) for x in leaves}


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def smoke_one_chip(cfg, device, *, max_len: int = MAX_LEN,
                   requests=make_requests) -> list[str]:
    """Serve ``cfg`` on ``device`` cold and warm, check prefill/decode
    agreement; return the failures."""
    import jax
    from repro.launch.mesh import make_local_mesh
    from repro.launch.serve import BatchServer, left_pad

    fails = []
    t0 = time.perf_counter()
    with CompileLog() as comp:
        server = BatchServer(cfg, make_local_mesh(devices=[device]),
                             max_len=max_len, seed=SEED)
        jax.block_until_ready(server.params)
    n, dtypes = param_summary(server.params)
    print(f"params: {n} ({n / 1e9:.2f} B) dtype {','.join(sorted(dtypes))}"
          f"; init {time.perf_counter() - t0:.3f}s "
          f"(compile {comp.secs:.3f}s)")
    if n != cfg.param_count():
        fails.append(f"{n} params, config says {cfg.param_count()}")
    if dtypes != {cfg.compute_dtype}:
        fails.append(f"weights in {dtypes}, not {cfg.compute_dtype}")

    reqs = requests(cfg.vocab_size)
    print(f"requests: {len(reqs)} prompts of "
          f"{sorted(len(r.prompt) for r in reqs)} tokens, "
          f"{reqs[0].max_new} new tokens each, greedy, max_len {max_len}")
    with CompileLog() as comp:
        cold = server.serve(reqs)
    print(f"cold serve: prefill {cold['prefill_s']:.3f}s, decode "
          f"{cold['decode_s']:.3f}s, {comp.count} compilations in "
          f"{comp.secs:.3f}s")
    reqs = requests(cfg.vocab_size)
    with CompileLog() as comp:
        warm = server.serve(reqs)
    gen = sum(len(t) for t in warm["outputs"].values())
    print(f"warm serve: prefill {warm['prefill_s']:.6f}s, decode "
          f"{warm['decode_s']:.6f}s, "
          f"{gen / (warm['prefill_s'] + warm['decode_s']):.3f} generated "
          f"tok/s, {comp.count} compilations")
    if comp.count:
        fails.append(f"{comp.count} compilations in the warm serve")
    gen = tokens_of(warm)
    if not np.array_equal(gen, tokens_of(cold)):
        fails.append("cold and warm greedy tokens differ")

    # the last decode step saw prompt + gen[:-1]; a prefill of exactly
    # that sequence must give the same last-position logits
    seq = np.concatenate([left_pad(reqs), gen[:, :-1]], axis=1)
    want = server.prefill_fn(server.params, jax.numpy.asarray(seq))[0]
    print(f"check: last decode step vs prefill of {seq.shape[1]} tokens")
    fails += compare_logits(warm["last_logits"], want)
    print(f"peak device memory: {peak_bytes(device)} bytes")
    return fails


def forced_logits(server, seq: np.ndarray, start: int) -> np.ndarray:
    """Logits at positions ``start:`` of one forward pass of ``server``'s
    model over ``seq`` (teacher forcing along a fixed token history)."""
    import jax
    from repro.models import lm
    from repro.parallel.sharding import use_rules

    def fwd(params, tokens):
        with use_rules(server.rules):
            return lm.forward(server.cfg, params, tokens)[0][:, start:]

    return np.asarray(jax.jit(fwd)(server.params, jax.numpy.asarray(seq)))


def smoke_four_chips(cfg, devices, *, max_len: int = MAX_LEN,
                     requests=make_requests) -> list[str]:
    """Serve the same requests on one device and tensor-parallel over
    four, and force both models along the one-device token history;
    return the failures of their agreement.

    Free-running greedy decoding may part at a near-tie that bf16
    rounding decides differently: a row may differ from its first
    differing step on only if the two tokens' forced one-device logits
    there are within the logit bound of each other."""
    from repro.launch.mesh import make_local_mesh
    from repro.launch.serve import BatchServer, left_pad

    runs = {}
    probes = {}
    seq = None
    for label, mesh in (
            ("1 device", make_local_mesh(devices=devices[:1])),
            ("model axis 4", make_local_mesh(model_axis=4,
                                             devices=devices[:4]))):
        server = BatchServer(cfg, mesh, max_len=max_len, seed=SEED)
        wq = server.param_shardings["blocks"]["pos0"]["attn"]["wq"]
        print(f"{label}: mesh {dict(mesh.shape)}, wq sharding {wq.spec}")
        probes[label] = np.asarray(server.params["embed"][:8])
        reqs = requests(cfg.vocab_size)
        with CompileLog() as comp:
            stats = server.serve(reqs)
        print(f"{label}: prefill {stats['prefill_s']:.3f}s, decode "
              f"{stats['decode_s']:.3f}s ({comp.count} compilations in "
              f"{comp.secs:.3f}s)")
        tokens = tokens_of(stats)
        if seq is None:
            prompts = left_pad(reqs)
            seq = np.concatenate([prompts, tokens[:, :-1]], axis=1)
        runs[label] = (tokens, np.asarray(stats["last_logits"]),
                       forced_logits(server, seq, prompts.shape[1] - 1))
        del server, stats
    print("peak device memory: "
          f"{[peak_bytes(d) for d in devices[:4]]} bytes")

    fails = []
    (tok1, last1, forced1), (tok4, last4, forced4) = runs.values()
    if not np.array_equal(*probes.values()):
        fails.append("the two meshes initialised different weights")
    bound = LOGIT_RTOL * float(np.abs(forced1).max())
    diff = float(np.abs(forced4 - forced1).max())
    print(f"forced logits over {forced1.shape[1]} steps: max|diff| "
          f"{diff:.6g} (bound {bound:.6g})")
    if not np.isfinite(forced4).all() or diff > bound:
        fails.append(f"forced logits: max|diff| {diff:.6g} > {bound:.6g}")
    same = [i for i in range(len(tok1)) if np.array_equal(tok1[i], tok4[i])]
    print(f"greedy tokens: {len(same)}/{len(tok1)} rows agree")
    for i in sorted(set(range(len(tok1))) - set(same)):
        s = int(np.flatnonzero(tok1[i] != tok4[i])[0])
        a, b = tok1[i, s], tok4[i, s]
        gap = abs(float(forced1[i, s, a] - forced1[i, s, b]))
        print(f"  row {i} parts at step {s}: one device {a}, model axis 4 "
              f"{b}, forced logit gap {gap:.6g}")
        if gap > bound:
            fails.append(f"row {i}: step {s} is no near-tie ({gap:.6g})")
    fails += compare_logits(last4[same], last1[same])
    return fails


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "not running on it", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}; compile cache {enable_compile_cache()}")
    cfg = get_config(ARCH)
    if args.chips == 1:
        fails = smoke_one_chip(cfg, dev)
    else:
        fails = smoke_four_chips(cfg, devices)
    for f in fails:
        print(f"FAILED: {f}", file=sys.stderr)
    if fails:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
