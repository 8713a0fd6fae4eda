"""Batched serving driver: continuous-batching style decode loop.

Requests arrive with different prompt lengths; the server left-pads to
a slot width, prefills per-request (sequentially here; slot-parallel on
a real frontend), then decodes the whole batch in lock-step with one
jitted decode step per token — the standard static-batch TPU serving
shape. Sampling: greedy or temperature.

The weights are kept in the config's compute dtype (bf16 at the
published widths, f32 for the reduced configs) and placed with the
mesh's parameter shardings, on the devices the activations run on.

``serve`` marks its phases with ``jax.profiler.TraceAnnotation`` host
spans (``SPANS``; inert unless a profiler trace runs) and adds up the
work it did in ``BatchServer.counters``.

Run:  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b \
          --batch 4 --gen 32
      (published widths; ``--reduced`` serves the tiny same-family model)
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models import lm
from repro.parallel.sharding import make_rules, params_shardings, use_rules


# host spans of ``BatchServer.serve``, on the profiler's clock
SPAN_PREFILL = "serve.prefill"   # prompt transfer, prefill, its sync
SPAN_RNG = "serve.rng"           # sampling keys
SPAN_DECODE = "serve.decode"     # token transfer and decode dispatch
SPAN_SAMPLE = "serve.sample"     # argmax and the host sync on it
SPAN_COLLECT = "serve.collect"   # appending tokens to the requests
SPANS = (SPAN_PREFILL, SPAN_RNG, SPAN_DECODE, SPAN_SAMPLE, SPAN_COLLECT)


@dataclass
class ServeCounters:
    """Cumulative counts of the work ``BatchServer.serve`` did."""
    prompt_tokens: int = 0       # true prompt lengths
    prefill_positions: int = 0   # rows x padded width
    decode_steps: int = 0
    slots: int = 0               # rows x the wave's longest max_new
    tokens_kept: int = 0         # tokens appended to a request


@dataclass
class Request:
    id: int
    prompt: np.ndarray               # (len,) int32
    max_new: int = 16
    temperature: float = 0.0
    tokens_out: list[int] = field(default_factory=list)


def serving_steps(cfg, rules, max_len: int):
    """The jitted prefill and decode steps a BatchServer runs."""

    def _prefill(params, tokens):
        with use_rules(rules):
            return lm.prefill(cfg, params, tokens, max_len=max_len)

    def _decode(params, cache, tok, pos):
        with use_rules(rules):
            return lm.decode_step(cfg, params, cache, tok, pos)

    return jax.jit(_prefill), jax.jit(_decode, donate_argnums=(1,))


def left_pad(requests: list[Request]) -> np.ndarray:
    """(B, longest prompt) int32 batch, each prompt padded on the left."""
    plen = max(len(r.prompt) for r in requests)
    prompts = np.zeros((len(requests), plen), np.int32)
    for i, r in enumerate(requests):
        prompts[i, plen - len(r.prompt):] = r.prompt
    return prompts


class BatchServer:
    """Fixed-slot batched decoder (one model replica)."""

    def __init__(self, cfg, mesh, max_len: int = 256, seed: int = 0):
        assert not cfg.is_encdec, "serve.py drives decoder-only archs"
        cfg = dataclasses.replace(cfg, param_dtype=cfg.compute_dtype)
        self.cfg = cfg
        self.mesh = mesh
        self.max_len = max_len
        self.rules = make_rules(cfg, mesh)
        shapes, specs = lm.abstract_init(cfg)
        self.param_shardings = params_shardings(self.rules, shapes, specs)
        self.params = jax.jit(lambda k: lm.init(cfg, k)[0],
                              out_shardings=self.param_shardings)(
            jax.random.PRNGKey(seed))
        self.prefill_fn, self.decode_fn = serving_steps(cfg, self.rules,
                                                        max_len)
        self.counters = ServeCounters()

    def _sample(self, logits: jax.Array, temps: np.ndarray,
                key) -> np.ndarray:
        greedy = np.asarray(jnp.argmax(logits, axis=-1))
        if (temps <= 0).all():
            return greedy
        noisy = np.asarray(jax.random.categorical(
            key, logits / jnp.maximum(jnp.asarray(temps)[:, None], 1e-4)))
        return np.where(temps > 0, noisy, greedy)

    def serve(self, requests: list[Request]) -> dict:
        Span = jax.profiler.TraceAnnotation
        B = len(requests)
        prompts = left_pad(requests)
        plen = prompts.shape[1]
        t0 = time.perf_counter()
        with Span(SPAN_PREFILL):
            logits, cache = jax.block_until_ready(
                self.prefill_fn(self.params, jnp.asarray(prompts)))
        t_prefill = time.perf_counter() - t0

        temps = np.array([r.temperature for r in requests], np.float32)
        with Span(SPAN_RNG):
            key = jax.random.PRNGKey(0)
        max_new = max(r.max_new for r in requests)
        with Span(SPAN_SAMPLE):
            tok = self._sample(logits, temps, key)
        with Span(SPAN_COLLECT):
            for i, r in enumerate(requests):
                r.tokens_out.append(int(tok[i]))
        kept = B
        t0 = time.perf_counter()
        for t in range(1, max_new):
            with Span(SPAN_RNG):
                key, sub = jax.random.split(key)
            with Span(SPAN_DECODE):
                logits, cache = self.decode_fn(
                    self.params, cache, jnp.asarray(tok[:, None], jnp.int32),
                    jnp.int32(plen + t - 1))
            with Span(SPAN_SAMPLE):
                tok = self._sample(logits, temps, sub)
            with Span(SPAN_COLLECT):
                for i, r in enumerate(requests):
                    if len(r.tokens_out) < r.max_new:
                        r.tokens_out.append(int(tok[i]))
                        kept += 1
        t_decode = time.perf_counter() - t0

        c = self.counters
        c.prompt_tokens += sum(len(r.prompt) for r in requests)
        c.prefill_positions += B * plen
        c.decode_steps += max(max_new - 1, 0)
        c.slots += B * max_new
        c.tokens_kept += kept
        return {
            "prefill_s": t_prefill,
            "decode_s": t_decode,
            "outputs": {r.id: r.tokens_out for r in requests},
            "last_logits": logits,      # (B, V) logits of the last step
        }


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--reduced", action="store_true",
                    help="serve the tiny same-family model instead of "
                         "the published widths")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--model-axis", type=int, default=1)
    return ap.parse_args(argv)


def main() -> None:
    args = parse_args()
    enable_compile_cache()
    cfg = get_config(args.arch, reduced=args.reduced)
    mesh = make_local_mesh(model_axis=args.model_axis)
    server = BatchServer(cfg, mesh, max_len=128)
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size,
                                    rng.integers(4, 24)).astype(np.int32),
                    max_new=args.gen, temperature=0.7 * (i % 2))
            for i in range(args.batch)]
    stats = server.serve(reqs)
    gen = sum(len(t) for t in stats["outputs"].values())
    print(f"prefill {stats['prefill_s']:.3f}s, decode {stats['decode_s']:.3f}s"
          f", {gen / (stats['prefill_s'] + stats['decode_s']):.1f} "
          f"generated tok/s")
    for rid, toks in stats["outputs"].items():
        print(f"  req {rid}: {toks[:12]}...")


if __name__ == "__main__":
    main()
