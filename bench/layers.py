"""What a per-layer metric reads: one run's records, gathered in one object.

A metric is a file ``bench/metrics/<name>.py`` with ``read(ctx)``, which
returns a number or ``None`` where its run has nothing to read. ``ctx`` is a
:class:`Context`:

  * ``cell``: the cell (configuration ``cell["config"]``, traffic mix
    ``cell["mix"]``);
  * ``family``: the configuration's model family (``bench/families``),
    whose ``counts`` give operations and bytes from its sizes;
  * ``rec``: the run's records (requests with their timestamps, every
    token's emit time, the load generator's lateness, the window
    ``t0``..``t1`` on the host clock);
  * ``spans``: the program's own ``Tracer`` events (engine steps with their
    args, per-request spans);
  * ``trace``: the profiler trace of the end of the window, reduced by
    ``bench/trace_reduce.py`` (device busy time, per-kernel device time and
    calls), over host times ``prof_t0``..``prof_t1``;
  * ``peaks``: the chip's published peaks (``bench/peaks.py``).
"""

from __future__ import annotations

import bisect

from bench import families
from bench import peaks as PK

STEP_SPANS = ("mixed_step", "decode_step")


class Context:
    def __init__(self, cell: dict, rec: dict, trace: dict, dev):
        self.cell, self.rec, self.trace = cell, rec, trace
        self.config, self.mix = cell["config"], cell["mix"]
        self.family = families.get(self.config)
        self.spans = rec["spans"]
        self.peaks = PK.peaks(dev.device_kind)
        self.prof_t0, self.prof_t1 = rec["prof"].t_ready, rec["prof"].t_stop
        self._emits = None
        self._chunks = None

    def steps(self, lo: float, hi: float, names=STEP_SPANS) -> list:
        """Engine step spans dispatched in [lo, hi) of the host clock."""
        return [e for e in self.spans if e.name in names and lo <= e.ts < hi]

    def window_steps(self, names=STEP_SPANS) -> list:
        return self.steps(self.rec["t0"], self.rec["t1"], names)

    def traced_steps(self, names=STEP_SPANS) -> list:
        return self.steps(self.prof_t0, self.prof_t1, names)

    def emitted_before(self, rid: int, t: float) -> int:
        """Tokens request ``rid`` had emitted before host time ``t``."""
        if self._emits is None:
            self._emits = {}
            for r, te in self.rec["emits"]:
                self._emits.setdefault(r, []).append(te)
        return bisect.bisect_left(self._emits.get(rid, []), t)

    def decode_contexts(self, t: float) -> list[int]:
        """Context length of every request decoding at host time ``t``: its
        prompt plus the tokens it had emitted (those in flight are a step or
        two at most)."""
        out = []
        for r in self.rec["requests"].values():
            if r.t_first and r.t_first <= t and (not r.t_done or t < r.t_done):
                out.append(len(r.prompt) + self.emitted_before(r.rid, t))
        return out

    def prefill_chunks(self, e) -> list[tuple[int, int]]:
        """(offset in its prompt, tokens) of each prefill chunk mixed step
        ``e`` carried, from the program's ``prefill_chunk[i]`` spans (they
        share the step's start time)."""
        if self._chunks is None:
            done: dict = {}
            self._chunks = {}
            for s in self.spans:
                if s.name.startswith("prefill_chunk["):
                    rid, n = s.args["rid"], s.args["tokens"]
                    self._chunks.setdefault(s.ts, []).append((done.get(rid, 0), n))
                    done[rid] = done.get(rid, 0) + n
        return self._chunks.get(e.ts, []) if e.name == "mixed_step" else []

    def step_flops(self, e) -> float:
        """Model operations of the REAL tokens of one step, padding left
        out: every decode lane and prefill token through the layers,
        attention over its own context, the head where a token is sampled."""
        c, F = self.config, self.family.counts
        total = sum(F.token_flops(c, n, head=True) for n in self.decode_contexts(e.ts))
        for off, n in self.prefill_chunks(e):
            total += n * F.linear_ops(c) + F.attn_ops_range(c, off + 1, off + n) + F.head_ops(c)
        return total
