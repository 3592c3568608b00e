"""Open-loop chat serving on the program's continuous-batching server.

The program's own path: ``launch/serve.Server`` over one
``core.generate.DecodeSession``, built from the configuration file:

    model       {"arch": a registry name, and any ModelConfig field}
    impl        {"attn": ..., "ssd": ...}, as ``configs.base.ImplContext``
    serve       {"max_batch", "max_len"}
    reference   the plain reference, reference/<reference>.py:
                ``weights(key, layout, config)`` gives the parameters in
                the program's layout (``layout``: their shapes) in one
                jitted call; ``logits(params, tokens, config, low=False)``
                the float32 logits (rows, positions, vocab) of a full
                forward over right-padded rows, ``low`` for the control
    reference_rows  rows per reference call
    limits      {compared number: limit}

The mix (schedule.py) gives the arrivals and lengths; the whole schedule
comes from ``--seed``. Set-up admits one request of every prompt length
the mix can produce, so every program the window runs is compiled. The
window submits on the schedule, open loop, for ``--seconds``, then waits
for every request submitted in it; one not finished ``GRACE_S`` after the
close counts as failed. Each request's time to first token runs from its
scheduled arrival to the host's receipt of that token; the gaps between
its tokens' receipts make the time between tokens.

``Tap`` reads each token's receipt time, log-probability and entropy off
the server's own loop: it wraps the session's ``prefill_into`` and
``step`` and the server's ``_took`` on the instance. The check takes
``check_requests`` finished requests (drawn from the seed, with the
longest sampled and the longest greedy one), runs the reference over
prompt + served tokens and compares, per served token, the
log-probability and the entropy at the request's temperature
(``logprob_gap``, ``entropy_gap``, the widest over sampled requests) and,
for greedy requests, how far the served token's logit lies below the
reference's best (``greedy_gap``).
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import importlib
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from chip import harness, schedule, weights
from chip.harness import log

GRACE_S = 60.0
clock = time.perf_counter


def _tuples(v):
    return tuple(_tuples(x) for x in v) if isinstance(v, list) else v


def model_config(config: dict):
    """The program's ModelConfig that the configuration file describes."""
    from repro.configs import get_config
    from repro.configs.base import ImplContext
    over = {k: _tuples(v) for k, v in config["model"].items() if k != "arch"}
    cfg = dataclasses.replace(get_config(config["model"]["arch"]), **over)
    return ImplContext(**config.get("impl", {})).apply(cfg)


def reference_module(config: dict):
    return importlib.import_module(f"chip.reference.{config['reference']}")


def percentile(values, q):
    """numpy's percentile (linear) of ``values``; None where empty."""
    return float(np.percentile(values, q)) if len(values) else None


# end-to-end statistics in ms: (the window's series, percentile); a cell
# reports those that its metrics name, and the window logs them all (the
# time to first token and the 99th gap spread too widely to be bounded)
STATS = {"ttft_p50_ms": ("ttft_s", 50), "ttft_p90_ms": ("ttft_s", 90),
         "tbt_p50_ms": ("gaps_s", 50), "tbt_p90_ms": ("gaps_s", 90),
         "tbt_p99_ms": ("gaps_s", 99)}


# -- reading the served tokens ----------------------------------------------

@dataclasses.dataclass
class Record:
    """One request's generated tokens as the host received them."""
    times: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    logprob: list = dataclasses.field(default_factory=list)
    entropy: list = dataclasses.field(default_factory=list)


@jax.jit
def _keep_row(new, old, slot):
    """``new`` with batch row ``slot`` taken from ``old`` (cache leaves
    carry the batch on axis 1, the rest on axis 0)."""
    cache = jax.tree.map(lambda n, o: n.at[:, slot].set(o[:, slot]),
                         new["cache"], old["cache"])
    rest = {k: v.at[slot].set(old[k][slot]) for k, v in new.items()
            if k != "cache"}
    return dict(rest, cache=cache)


class Tap:
    """Each generated token's receipt time, log-probability and entropy,
    per request, from the server's own loop. ``fault`` plants one fault
    for a calibration: ("state_unchanged", handle, k): the request's slot
    keeps its decode state through the step that makes its token k+1;
    ("first_logprob_other_slot", handle): from that request on, each
    request's first token's log-probability is the one of the request
    admitted before it (one swapped pair can read alike by chance)."""

    def __init__(self, server):
        self.server = server
        self.records = {}          # RequestHandle -> Record
        self.admissions = 0
        self.fault = None
        sess = server.session
        self._prefill, self._step = sess.prefill_into, sess.step
        self._took = server._took
        sess.prefill_into, sess.step = self._on_prefill, self._on_step
        server._took = self._on_took
        self._first = {}           # slot -> (receipt time, prefill's out)
        self._out = self._t_step = None
        self._prev_lp = None
        self.planted = False       # the fault took effect

    def _on_prefill(self, slot, prompt, **kw):
        out = self._prefill(slot, prompt, **kw)
        self._first[slot] = (clock(), out)
        self.admissions += 1
        return out

    def _slot_of(self, handle):
        for slot, req in list(self.server._inflight.items()):
            if req.handle is handle:
                return slot
        return None

    def _on_step(self):
        keep = None
        if self.fault and self.fault[0] == "state_unchanged":
            _, handle, k = self.fault
            slot = self._slot_of(handle)
            rec = self.records.get(handle)
            if slot is not None and rec and len(rec.tokens) == k:
                sess = self.server.session
                keep = (slot, jax.tree.map(jnp.copy, sess._state))
        out = self._step()
        if keep:
            sess._state = _keep_row(sess._state, keep[1], jnp.int32(keep[0]))
            self.fault, self.planted = None, True
        self._t_step, self._out = clock(), out
        return out

    def _on_took(self, slot, token):
        req = self.server._inflight[slot]
        first = self._first.pop(slot, None)
        if first is not None:
            t, out = first
            lp, ent = float(out["logprob"]), float(out["entropy"])
            if (self.fault and self.fault[0] == "first_logprob_other_slot"
                    and self._prev_lp is not None
                    and (self.planted or self.fault[1] is req.handle)):
                lp, self.planted = self._prev_lp, True
            self._prev_lp = float(out["logprob"])
        else:
            t, out = self._t_step, self._out
            lp = float(out["logprob"][slot])
            ent = float(out["entropy"][slot])
        rec = self.records.setdefault(req.handle, Record())
        rec.times.append(t)
        rec.tokens.append(int(token))
        rec.logprob.append(lp)
        rec.entropy.append(ent)
        self._took(slot, token)


# -- the reference's view of the served tokens ---------------------------------

@jax.jit
def _token_stats(logits, tokens, temps):
    """Per position j < S-1 of each row, for the token at j+1: its
    log-probability and the entropy at the row's temperature, its logit's
    gap below the largest, and the largest logit's token."""
    lg = logits[:, :-1]
    nxt = tokens[:, 1:, None]
    lp_all = jax.nn.log_softmax(lg / temps[:, None, None], axis=-1)
    lp = jnp.take_along_axis(lp_all, nxt, -1)[..., 0]
    ent = -jnp.sum(jnp.exp(lp_all) * lp_all, axis=-1)
    gap = lg.max(-1) - jnp.take_along_axis(lg, nxt, -1)[..., 0]
    return lp, ent, gap, jnp.argmax(lg, -1).astype(jnp.int32)


@jax.jit
def _gap_at(logits, picks):
    """How far the logit of ``picks`` (the token each position's other
    computation puts first) lies below each position's largest."""
    lg = logits[:, :-1]
    return lg.max(-1) - jnp.take_along_axis(lg, picks[..., None], -1)[..., 0]


def compare(rows, ref_logits_fn, rows_per_call, max_len, control_fn=None):
    """The compared numbers of ``rows`` (dicts: prompt, tokens, logprob,
    entropy, temperature, greedy) against the reference's logits; with
    ``control_fn``, the control's numbers on the same prompts and served
    tokens too. Returns (program's, control's or None)."""
    prog = {"logprob_gap": [], "entropy_gap": [], "greedy_gap": []}
    ctl = {k: [] for k in prog}
    for b in range(0, len(rows), rows_per_call):
        block = rows[b:b + rows_per_call]
        toks = np.zeros((rows_per_call, max_len), np.int32)
        temps = np.ones((rows_per_call,), np.float32)
        for r, row in enumerate(block):
            seq = np.concatenate([row["prompt"], row["tokens"]])
            toks[r, :len(seq)] = seq
            temps[r] = row["temperature"]
        toks_d, temps_d = jnp.asarray(toks), jnp.asarray(temps)
        ref = ref_logits_fn(toks_d)
        lp, ent, gap, _ = (np.asarray(x) for x in
                           _token_stats(ref, toks_d, temps_d))
        if control_fn is not None:
            low = control_fn(toks_d)
            c_lp, c_ent, _, c_top = _token_stats(low, toks_d, temps_d)
            c_gap = np.asarray(_gap_at(ref, c_top))
            c_lp, c_ent = np.asarray(c_lp), np.asarray(c_ent)
            del low
        del ref
        for r, row in enumerate(block):
            p, n = len(row["prompt"]), len(row["tokens"])
            at = slice(p - 1, p - 1 + n)
            if row["greedy"]:
                prog["greedy_gap"].append(float(np.max(gap[r, at])))
                if control_fn is not None:
                    ctl["greedy_gap"].append(float(np.max(c_gap[r, at])))
                continue
            prog["logprob_gap"].append(float(np.max(np.abs(
                np.asarray(row["logprob"]) - lp[r, at]))))
            prog["entropy_gap"].append(float(np.max(np.abs(
                np.asarray(row["entropy"]) - ent[r, at]))))
            if control_fn is not None:
                ctl["logprob_gap"].append(float(np.max(np.abs(
                    c_lp[r, at] - lp[r, at]))))
                ctl["entropy_gap"].append(float(np.max(np.abs(
                    c_ent[r, at] - ent[r, at]))))

    def widest(d):
        return {k: max(v) if v else float("inf") for k, v in d.items()}
    return widest(prog), (widest(ctl) if control_fn is not None else None)


# -- the cell ------------------------------------------------------------------

class Run:
    def __init__(self, cell, args, devices):
        if len(devices) != 1:
            raise ValueError("the chat runner drives one chip")
        self.cell, self.args, self.devices = cell, args, devices
        self.cfg, self.mix = cell.config, cell.traffic
        self.model_cfg = model_config(self.cfg)
        self.ref = reference_module(self.cfg)
        serve = self.cfg["serve"]
        self.max_batch, self.max_len = serve["max_batch"], serve["max_len"]
        self.e2e, self.counters = {}, {}
        self.attempted = self.failed = 0
        self.server = self.tap = self.params = None
        self.served = None

    # -- building the program ----------------------------------------------

    def _weights(self, seed):
        from repro.models import model as model_lib
        layout = jax.eval_shape(lambda k: model_lib.init(k, self.model_cfg)[0],
                                jax.random.PRNGKey(0))
        params = self.ref.weights(weights.seed_key(seed, "weights"), layout,
                                  self.cfg)
        return jax.block_until_ready(params)

    def prompt_lengths(self):
        """Each prompt length the mix can produce, one per prefill program
        of the session (``core.generate.prefill_len``)."""
        from repro.core.generate import prefill_len
        by_program = {}
        for p in schedule.prompt_lengths(self.mix):
            if 0 < p < self.max_len:
                by_program.setdefault(
                    prefill_len(self.model_cfg, p, self.max_len), p)
        if len(by_program) > 32:
            raise harness.SetupError(
                f"{len(by_program)} prefill programs: give the mix a "
                "prompt_ladder")
        return sorted(by_program.values())

    def _start_server(self):
        from repro.launch.serve import Server
        self.server = Server(self.model_cfg, self.params,
                             max_batch=self.max_batch, max_len=self.max_len)
        self.tap = Tap(self.server)
        self.server.start()

    def _free_server(self):
        """Stop the server and free its decode state (the weights stay)."""
        if self.server is not None:
            self.server.stop()
            self.server.session._state = None
            self.server = self.tap = None
            gc.collect()

    def _both_kinds(self, seconds):
        """The check compares sampled and greedy requests: a window has to
        send at least one of each."""
        n = schedule.requests(self.mix, seconds)
        g = schedule.greedy_requests(self.mix, n)
        if not 0 < g < n:
            raise harness.SetupError(
                f"{n} requests a window with {g} greedy: the check needs "
                "sampled and greedy ones; set the mix's greedy_share")

    def setup(self):
        t0 = clock()
        self._both_kinds(min(self.args.seconds,
                             float(self.mix["trace_seconds"]))
                         if self.args.trace else self.args.seconds)
        self.params = self._weights(self.args.seed)
        self._start_server()
        rng = np.random.default_rng(0)
        lengths = self.prompt_lengths()
        handles = [self.server.submit(
            rng.integers(0, self.model_cfg.vocab_size, p, dtype=np.int32),
            max_tokens=2, key=np.array([0, i], np.uint32))
            for i, p in enumerate(lengths)]
        for h in handles:
            h.result(timeout=1200)
        log(f"set-up: weights and server, warmed prompt lengths {lengths}: "
            f"{clock() - t0:.1f}s")

    # -- the measured window ------------------------------------------------

    def _serve(self, sched, seconds, tracer=None):
        """Submit ``sched`` open loop, wait for its requests; the window's
        readings."""
        server, tap = self.server, self.tap
        n = len(sched)
        handles, depth = [None] * n, []
        adm0, steps0 = tap.admissions, server.steps
        if tracer:
            tracer.start()
        t0 = clock()
        for i in range(n):
            wait = t0 + sched.arrive_s[i] - clock()
            if wait > 0:
                time.sleep(wait)
            handles[i] = server.submit(
                sched.prompts[i], max_tokens=int(sched.output_len[i]),
                temperature=float(sched.temperature[i]), key=sched.keys[i])
            depth.append((clock() - t0, len(server._queue)))
        close = t0 + seconds
        if clock() < close:
            time.sleep(close - clock())
        done_in_window = sum(h.done() for h in handles)
        queue_at_close = len(server._queue)
        failed = 0
        for i, h in enumerate(handles):
            try:
                out = h.result(timeout=max(0.0, close + GRACE_S - clock()))
                want = min(int(sched.output_len[i]),
                           self.max_len - len(sched.prompts[i]))
                failed += len(out) != len(sched.prompts[i]) + want
            except Exception as e:  # noqa: BLE001 - never came, or failed
                log(f"request {i}: {type(e).__name__}: {e}")
                failed += 1
        end = clock()
        if tracer:
            tracer.stop()
        recs = [tap.records.pop(h, Record()) for h in handles]
        ttft = [r.times[0] - (t0 + a) for r, a in zip(recs, sched.arrive_s)
                if r.times]
        gaps = np.concatenate([np.diff(r.times) for r in recs] + [[]])
        tokens = sum(len(r.tokens) for r in recs)
        return {"handles": handles, "records": recs, "sent": n,
                "failed": failed, "done_in_window": done_in_window,
                "queue_at_close": queue_at_close,
                "ttft_s": ttft, "gaps_s": gaps, "tokens": tokens,
                "elapsed_s": end - t0, "drain_s": end - close,
                "depth": depth, "admissions": tap.admissions - adm0,
                "decode_steps": server.steps - steps0}

    def window(self, seconds, tracer):
        sched = schedule.draw(self.mix, self.args.seed, seconds,
                              self.model_cfg.vocab_size)
        got = self._serve(sched, seconds, tracer)
        self.sched, self.served = sched, got
        self.attempted, self.failed = got["sent"], got["failed"]
        for name, (series, q) in STATS.items():
            value = percentile(got[series], q)
            if value is not None:
                self.e2e[name] = 1e3 * value
        self.counters.update(
            requests=got["sent"], admissions=got["admissions"],
            decode_steps=got["decode_steps"], tokens=got["tokens"],
            window_s=got["elapsed_s"])
        log(f"window: {got['sent']} requests sent in {seconds:.1f}s, "
            f"{got['done_in_window']} done by its close, {got['failed']} "
            f"failed; {got['tokens']} tokens in {got['elapsed_s']:.3f}s "
            f"({got['tokens'] / got['elapsed_s']:.1f} tokens/s, drained "
            f"{got['drain_s']:.3f}s after the close); peak queue depth "
            f"{max((d for _, d in got['depth']), default=0)}; "
            f"{got['decode_steps']} decode steps; "
            + ", ".join(f"{k} {v}" for k, v in self.e2e.items()))

    def hlo_texts(self):
        """{program: compiled HLO text} of the session's decode step, which
        names the scope of each of its instructions (chip.spans)."""
        sess = self.server.session
        step = sess._fns.step
        args = harness.abstract((sess.params, sess._state))
        return {"jit_step": step.lower(*args).compile().as_text()}

    # -- correctness --------------------------------------------------------

    def _sample(self, sched, got, seed):
        """Indices of ``check_requests`` finished requests drawn from the
        seed, with the longest sampled and the longest greedy one."""
        done = [i for i, (h, r) in enumerate(zip(got["handles"],
                                                 got["records"]))
                if h.done() and r.tokens]
        rng = np.random.default_rng([int(seed) % 2**64, 17])
        greedy = sched.greedy
        pick = []
        for group in ([i for i in done if not greedy[i]],
                      [i for i in done if greedy[i]]):
            if group:
                pick.append(max(group, key=lambda i: (
                    len(got["records"][i].tokens), -i)))
        rest = [i for i in rng.permutation(done) if i not in pick]
        return pick + rest[:max(0, self.mix["check_requests"] - len(pick))]

    def _rows(self, sched, got, sample):
        return [{"prompt": sched.prompts[i],
                 "tokens": np.asarray(got["records"][i].tokens, np.int32),
                 "logprob": got["records"][i].logprob,
                 "entropy": got["records"][i].entropy,
                 "temperature": float(sched.temperature[i]),
                 "greedy": bool(sched.greedy[i])} for i in sample]

    def _readings(self, sched, got, seed, control=False):
        rows = self._rows(sched, got, self._sample(sched, got, seed))
        params, cfg = self.params, self.cfg
        ref = functools.partial(self.ref.logits, params, config=cfg)
        low = (functools.partial(self.ref.logits, params, config=cfg,
                                 low=True) if control else None)
        return compare(rows, ref, int(cfg.get("reference_rows", 4)),
                       self.max_len, low)

    def check(self):
        """Every number of the configuration's ``limits``; one that no
        checked request gives reads inf, and fails."""
        self._free_server()
        got, _ = self._readings(self.sched, self.served, self.args.seed)
        return {k: (got.get(k, float("inf")), lim)
                for k, lim in self.cfg["limits"].items()}

    def calibrate(self):
        """Per seed from --seed: a window at the cell's load, the program's
        readings and the control's on its requests, then the same schedule
        again with each fault planted in the timed path, and their
        readings, one JSON line a seed."""
        self.setup()
        self._free_server()
        seconds = self.args.seconds
        for s in range(self.args.seed, self.args.seed + self.args.calibrate):
            t0 = clock()
            if s != self.args.seed:
                self.params = self._weights(s)
            sched = schedule.draw(self.mix, s, seconds,
                                  self.model_cfg.vocab_size)
            got = self._serve_fresh(sched, seconds)
            prog, _ = self._readings(sched, got, s)
            out = {"seed": s, "sent": got["sent"], "failed": got["failed"],
                   "program": prog}
            try:
                _, out["control"] = self._readings(sched, got, s,
                                                   control=True)
            except Exception as e:  # noqa: BLE001 - e.g. out of memory
                out["control"] = f"{type(e).__name__}: {str(e)[:300]}"
            # a sampled request that another one was admitted before
            target = next(i for i in self._sample(sched, got, s)
                          if i > 0 and not sched.greedy[i])
            for name, fault in (("state_unchanged", ("state_unchanged", 2)),
                                ("first_logprob_other_slot",
                                 ("first_logprob_other_slot",))):
                bad = self._serve_fresh(sched, seconds, (target, fault))
                out[name], _ = self._readings(sched, bad, s)
                out[name]["failed"] = bad["failed"]
            out["seconds"] = clock() - t0
            print(json.dumps(out), flush=True)

    def _serve_fresh(self, sched, seconds, fault=None):
        """``_serve`` on a server of its own, freed before the reference
        runs; ``fault``: (request index, the Tap's fault without the
        handle), planted as that request is submitted."""
        self._start_server()
        if fault is not None:
            target, (kind, *rest) = fault
            real, count = self.server.submit, [0]

            def submit(prompt, **kw):
                h = real(prompt, **kw)
                if count[0] == target:
                    self.tap.fault = (kind, h, *rest)
                count[0] += 1
                return h
            self.server.submit = submit
        try:
            got = self._serve(sched, seconds)
            if fault is not None and not self.tap.planted:
                raise RuntimeError(f"fault {fault} was never planted")
            return got
        finally:
            self._free_server()

    def sweep(self, rates):
        """For each rate: the window at that rate, and whether the server
        kept pace: no request failed, the queue's mean depth in the
        window's last quarter at most 2 above its first quarter's, and at
        the close no more requests unfinished than fill the slots and 2
        more."""
        self.setup()
        seconds = self.args.seconds
        for rate in rates:
            mix = dict(self.mix, rate_per_s=rate)
            sched = schedule.draw(mix, self.args.seed, seconds,
                                  self.model_cfg.vocab_size)
            got = self._serve(sched, seconds)
            q = [[d for t, d in got["depth"]
                  if k * seconds / 4 <= t < (k + 1) * seconds / 4]
                 for k in range(4)]
            mean_q = [float(np.mean(x)) if x else 0.0 for x in q]
            out = {"rate_per_s": rate, "sent": got["sent"],
                   "failed": got["failed"],
                   "done_in_window": got["done_in_window"],
                   "queue_at_close": got["queue_at_close"],
                   "queue_mean_by_quarter": mean_q,
                   "queue_peak": max((d for _, d in got["depth"]),
                                     default=0),
                   "drain_s": got["drain_s"],
                   "tokens_per_s": got["tokens"] / got["elapsed_s"],
                   "ttft_p50_s": percentile(got["ttft_s"], 50),
                   "ttft_p90_s": percentile(got["ttft_s"], 90),
                   "tbt_p50_s": percentile(got["gaps_s"], 50),
                   "tbt_p99_s": percentile(got["gaps_s"], 99),
                   "decode_steps": got["decode_steps"]}
            out["kept_pace"] = (
                got["failed"] == 0 and mean_q[3] <= mean_q[0] + 2
                and got["sent"] - got["done_in_window"]
                <= self.max_batch + 2)
            print(json.dumps(out), flush=True)
        self._free_server()
