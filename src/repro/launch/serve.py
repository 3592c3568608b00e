"""Continuous-batching inference server on the DecodeSession API.

The PolyBeast inference-queue idea (keep accelerator evaluations batched)
taken to its serving conclusion: instead of draining fixed batches and
running each to completion (head-of-line blocking on the longest
generation), the server owns one ``core.generate.DecodeSession`` and
re-decides the batch EVERY step — finished requests are evicted and
queued requests admitted into the freed slots while the survivors keep
decoding. ``--policy static`` keeps the old drain-and-run behaviour as a
baseline; ``benchmarks/run.py --suite serving`` measures both.

Client API (request handles, not blocking arrays):

    h = server.submit(prompt, max_tokens=64, temperature=0.8,
                      stop_token=eos)
    tokens = h.result(timeout=30)     # (P + generated,) int32

A single-request server is bitwise-identical to ``core.generate.generate``
with the same seed (see tests/test_decode_session.py).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --reduced \
      --requests 24 --gen-tokens 16
"""

from __future__ import annotations

import argparse
import collections
import sys
import threading
import time

import jax
import numpy as np

from repro.configs import get_config, get_reduced_config
from repro.configs.base import ImplContext
from repro.core.generate import DecodeSession
from repro.launch.compile_cache import use_compile_cache
from repro.models import model as model_lib


class RequestHandle:
    """Future-style handle for one submitted request."""

    def __init__(self, prompt: np.ndarray):
        self.prompt = prompt
        self._event = threading.Event()
        self._tokens = None
        self._error = None
        self.t_submit = time.monotonic()
        self.t_first = None           # first generated token (prefill done)
        self.t_done = None

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: float | None = None) -> np.ndarray:
        """Block until complete; returns (P + generated,) int32 tokens
        (prompt echoed, stop token included when hit)."""
        if not self._event.wait(timeout):
            raise TimeoutError("request not complete")
        if self._error is not None:
            raise self._error
        return self._tokens

    # -- server side --------------------------------------------------------

    def _complete(self, tokens: np.ndarray) -> None:
        self._tokens = tokens
        self.t_done = time.monotonic()
        self._event.set()

    def _fail(self, err: Exception) -> None:
        self._error = err
        self.t_done = time.monotonic()
        self._event.set()


class _Request:
    __slots__ = ("handle", "prompt", "max_tokens", "temperature",
                 "stop_token", "key", "tokens", "slot")

    def __init__(self, handle, prompt, max_tokens, temperature, stop_token,
                 key):
        self.handle = handle
        self.prompt = prompt
        self.max_tokens = max_tokens
        self.temperature = temperature
        self.stop_token = stop_token
        self.key = key
        self.tokens: list = []


class Server:
    """Continuous-batching server over one DecodeSession.

    policy='continuous': admission/eviction every step (default).
    policy='static':     admit only into an EMPTY batch and run it until
                         every member finishes — the fixed-batch baseline.
    """

    def __init__(self, cfg, params, *, max_batch: int = 8,
                 max_len: int = 256, policy: str = "continuous",
                 default_max_tokens: int = 16, mesh=None, rules=None,
                 seed: int = 0):
        if policy not in ("continuous", "static"):
            raise ValueError(f"unknown policy {policy!r}")
        self.cfg = cfg
        self.policy = policy
        self.default_max_tokens = default_max_tokens
        self.session = DecodeSession(params, cfg, max_batch=max_batch,
                                     max_len=max_len, mesh=mesh, rules=rules)
        self._key = jax.random.PRNGKey(seed)
        self._cv = threading.Condition()
        self._queue: collections.deque = collections.deque()
        self._inflight: dict = {}     # slot -> _Request
        self._closed = False
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self.served = 0
        self.steps = 0                # decode steps executed
        self.tokens_out = 0           # generated tokens (incl. prefill's)

    def start(self) -> "Server":
        self._thread.start()
        return self

    def stop(self) -> None:
        """Close the queue; in-flight and queued requests still complete."""
        with self._cv:
            self._closed = True
            self._cv.notify()
        self._thread.join(timeout=60)

    def submit(self, prompt, *, max_tokens: int | None = None,
               temperature: float = 1.0, stop_token: int | None = None,
               key=None) -> RequestHandle:
        """Enqueue a request (any thread). ``key`` pins the sampling PRNG
        key (parity tests); None draws from the server's stream."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if not 0 < prompt.shape[0] < self.session.max_len:
            raise ValueError(
                f"prompt length {prompt.shape[0]} not in "
                f"[1, {self.session.max_len})")
        handle = RequestHandle(prompt)
        n = max_tokens if max_tokens is not None else self.default_max_tokens
        n = min(n, self.session.max_len - prompt.shape[0])
        with self._cv:
            if self._closed:
                raise RuntimeError("server is stopped")
            if key is None:
                self._key, key = jax.random.split(self._key)
            self._queue.append(_Request(handle, prompt, n, temperature,
                                        stop_token, np.asarray(key)))
            self._cv.notify()
        return handle

    # -- server thread ------------------------------------------------------

    def _free_slot(self):
        """First slot neither active nor reserved by a pending admission."""
        active = self.session.active
        for s in range(self.session.max_batch):
            if not active[s] and s not in self._inflight:
                return s
        return None

    def _admissible(self) -> bool:
        if not self._queue or self._free_slot() is None:
            return False
        return self.policy == "continuous" or not self._inflight

    def _finish(self, slot: int) -> None:
        req = self._inflight.pop(slot)
        self.session.evict(slot)
        req.handle._complete(np.concatenate(
            [req.prompt, np.asarray(req.tokens, np.int32)]))
        self.served += 1

    def _took(self, slot: int, token: int) -> None:
        """Record one generated token; finish the request on stop/budget."""
        req = self._inflight[slot]
        req.tokens.append(token)
        self.tokens_out += 1
        if req.handle.t_first is None:
            req.handle.t_first = time.monotonic()
        if token == req.stop_token or len(req.tokens) >= req.max_tokens:
            self._finish(slot)

    def _loop(self) -> None:
        while True:
            reqs = []
            with self._cv:
                while (not self._closed and not self._queue
                       and not self._inflight):
                    self._cv.wait(timeout=0.5)
                if (self._closed and not self._queue
                        and not self._inflight):
                    return
                while self._admissible():
                    # reserve the slot now so _admissible stays accurate
                    slot = self._free_slot()
                    req = self._queue.popleft()
                    req.slot = slot
                    self._inflight[slot] = req
                    reqs.append(req)
            for req in reqs:   # prefill outside the lock (slow)
                slot = req.slot
                try:
                    out = self.session.prefill_into(
                        slot, req.prompt, key=req.key,
                        temperature=req.temperature)
                except Exception as e:  # noqa: BLE001
                    self._inflight.pop(slot)
                    req.handle._fail(e)
                    continue
                self._took(slot, int(out["token"]))
            if self._inflight:
                out = self.session.step()
                self.steps += 1
                for slot in list(self._inflight):
                    self._took(slot, int(out["token"][slot]))


def main(argv=None):
    use_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="qwen3-4b")
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--requests", type=int, default=24)
    p.add_argument("--prompt-len", type=int, default=15,
                   help="max prompt length (lengths drawn in [1, this])")
    p.add_argument("--gen-tokens", type=int, default=16,
                   help="max generation budget (per-request budgets drawn "
                        "in [1, this])")
    p.add_argument("--max-batch", type=int, default=8)
    p.add_argument("--max-len", type=int, default=0,
                   help="slot capacity (0: prompt-len + gen-tokens)")
    p.add_argument("--policy", default="continuous",
                   choices=["continuous", "static"])
    p.add_argument("--attn-impl", default=None,
                   choices=["xla", "xla_chunked", "xla_chunked_skip",
                            "kernel"],
                   help="'kernel': Pallas flash kernel for prefill + "
                        "decode-attention kernel per generated token "
                        "(interpret-mode on CPU)")
    p.add_argument("--ssd-impl", default=None, choices=["xla", "kernel"],
                   help="Mamba2 chunk-scan impl for prefill")
    args = p.parse_args(argv)

    cfg = (get_reduced_config if args.reduced else get_config)(args.arch)
    cfg = ImplContext.from_args(args).apply(cfg)
    params, _ = model_lib.init(jax.random.PRNGKey(0), cfg)
    max_len = args.max_len or args.prompt_len + args.gen_tokens
    server = Server(cfg, params, max_batch=args.max_batch, max_len=max_len,
                    policy=args.policy,
                    default_max_tokens=args.gen_tokens).start()

    rng = np.random.default_rng(0)
    t0 = time.time()
    handles = []
    for _ in range(args.requests):
        plen = int(rng.integers(1, args.prompt_len + 1))
        prompt = rng.integers(0, cfg.vocab_size, size=plen)
        handles.append(server.submit(
            prompt, max_tokens=int(rng.integers(1, args.gen_tokens + 1))))
    results = [h.result(timeout=600) for h in handles]
    dt = time.time() - t0
    server.stop()

    ok = all(np.array_equal(r[:h.prompt.shape[0]], h.prompt)
             for r, h in zip(results, handles))
    print(f"served {server.served} requests / {server.tokens_out} tokens "
          f"in {server.steps} decode steps ({dt:.2f}s, "
          f"{server.tokens_out/dt:.0f} tok/s, policy={args.policy}); "
          f"prompt-echo check: {'OK' if ok else 'FAIL'}")
    if not ok or server.served != args.requests:
        sys.exit(1)


if __name__ == "__main__":
    main()
