"""Traffic for the benchmark: request sets from a mix file, and the drivers
that offer them to a `ServingFrontend`.

`LengthMix` is the shareGPT-shaped lognormal of the serving stack's own load
generator (lognormal around the geometric middle of each range, clipped to
it), kept here so that a change to the program cannot move the yardstick.
Two things differ: lengths come as one fixed cycle of CYCLE requests,
repeated, so every seed serves the same sizes in the same order and a
window holds whole cycles but one (the seed draws the token ids and where
in the cycle the sequence starts, never the amount of work or how long
prompts bunch), and prompt lengths are few, so set-up can warm every one
of them.

Two loops:

  * closed: each of C clients submits its next request the moment its
    previous one finishes (callers that each wait for their reply);
  * open: arrivals on a seeded schedule, Poisson or two-state bursty, no
    matter what is in flight (independent users).

Both record, per request, the submit time and the arrival time of every
token at the client, on the frontend's clock; `Window` turns those records
into the end-to-end metrics.
"""

from __future__ import annotations

import asyncio
import dataclasses
import math
from statistics import NormalDist

import numpy as np

CYCLE = 16      # requests per cycle: each prompt-length quantile once


@dataclasses.dataclass(frozen=True)
class LengthMix:
    prompt_min: int
    prompt_max: int
    new_min: int
    new_max: int
    sigma: float = 0.6

    def quantiles(self, lo: int, hi: int, n: int) -> list[int]:
        """``n`` lengths at the mid-quantiles of the clipped lognormal."""
        median = math.sqrt(lo * hi)
        z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
        return [int(min(hi, max(lo, round(median * math.exp(self.sigma * v)))))
                for v in z]


@dataclasses.dataclass(frozen=True)
class Traffic:
    """One mix file (``bench/traffic/<name>.json``)."""

    name: str
    loop: str                   # 'closed' | 'open'
    slots: int                  # server lanes provisioned for the mix
    lengths: LengthMix
    clients: int = 0            # closed loop
    arrivals: dict | None = None   # open loop: {'kind', 'rate_rps', ...}

    @classmethod
    def from_json(cls, name: str, d: dict) -> "Traffic":
        lm = LengthMix(d["prompt"]["min"], d["prompt"]["max"],
                       d["output"]["min"], d["output"]["max"],
                       d.get("sigma", 0.6))
        t = cls(name=name, loop=d["loop"], slots=d["slots"], lengths=lm,
                clients=d.get("clients", 0), arrivals=d.get("arrivals"))
        if t.loop == "closed" and t.clients < 1:
            raise ValueError(f"{name}: a closed loop needs clients >= 1")
        if t.loop == "open" and not t.arrivals:
            raise ValueError(f"{name}: an open loop needs arrivals")
        if t.loop not in ("closed", "open"):
            raise ValueError(f"{name}: unknown loop {t.loop!r}")
        return t

    @property
    def cache_len(self) -> int:
        return self.lengths.prompt_max + self.lengths.new_max

    def prompt_lengths(self) -> list[int]:
        """The distinct prompt lengths the mix can send (set-up warms each)."""
        lm = self.lengths
        return sorted(set(lm.quantiles(lm.prompt_min, lm.prompt_max, CYCLE)))

    def fixed_draws(self) -> tuple[list[tuple[int, int]], list[float]]:
        """What every seed shares: the cycle of CYCLE (prompt length,
        budget) pairs in order, and each client's first-budget scale."""
        lm = self.lengths
        fixed = np.random.default_rng(0)
        plens = lm.quantiles(lm.prompt_min, lm.prompt_max, CYCLE)
        budgets = lm.quantiles(lm.new_min, lm.new_max, CYCLE)
        # One pairing of prompt and output lengths, in one order, for every
        # seed: the order decides how long prompts bunch, and with it the
        # TTFT tail.
        pairs = list(zip(fixed.permutation(plens).tolist(), budgets))
        cycle = [pairs[i] for i in fixed.permutation(CYCLE)]
        # First request of each client: budget scaled by a stratified draw
        # in (0, 1], so completions spread through the window instead of
        # arriving in one wave.
        n = max(self.clients, 1)
        return cycle, ((fixed.permutation(n) + 0.5) / n).tolist()

    def decode_positions(self) -> list[int]:
        """The position of the token each decode step takes in, one step per
        token the cycle generates.  A request with a prompt of p tokens and
        a budget of n takes in the token its prompt gave at p, then its own
        tokens, up to p+n-1."""
        cycle, _ = self.fixed_draws()
        return [q for p, n in cycle for q in range(p, p + n)]

    def prefill_chunks(self, chunk: int) -> list[tuple[int, int]]:
        """(first position, tokens) of each prompt chunk the cycle makes, in
        the scheduler's chunks of ``chunk``."""
        out = []
        for p, _ in self.fixed_draws()[0]:
            start = 0
            for c in chunk_schedule(p, chunk):
                out.append((start, c))
                start += c
        return out


def chunk_schedule(s: int, chunk: int) -> list[int]:
    """The scheduler's chunking of an s-token prompt: full chunks, then the
    remainder as descending powers of two."""
    out = [chunk] * (s // chunk)
    rem = s % chunk
    p = 1 << max(rem.bit_length() - 1, 0)
    while rem:
        if p <= rem:
            out.append(p)
            rem -= p
        p //= 2
    return out


@dataclasses.dataclass
class Requests:
    """The seeded request sequence of one run: one fixed cycle of CYCLE
    sizes, repeated, entered at a seeded point; token ids from the seed."""

    traffic: Traffic
    seed: int
    vocab: int

    def __post_init__(self):
        self._cycle, self._first_scale = self.traffic.fixed_draws()
        rng = np.random.default_rng(self.seed)
        self._start = int(rng.integers(0, CYCLE))
        self._token_seed = int(rng.integers(0, 2 ** 31))
        self._next = 0

    def take(self, first_of_client: int | None = None
             ) -> tuple[int, list[int], int]:
        """(uid, prompt ids, budget) of the next request."""
        uid = self._next
        self._next += 1
        plen, budget = self._cycle[(self._start + uid) % CYCLE]
        if first_of_client is not None:
            budget = max(1, round(budget * self._first_scale[first_of_client]))
        rng = np.random.default_rng((self._token_seed, uid))
        return uid, rng.integers(1, self.vocab, size=plen).tolist(), budget


def arrival_gaps(arrivals: dict, rng: np.random.Generator):
    """Seeded gaps of an open loop, without end: 'poisson', or 'bursty' (a
    two-state Markov-modulated Poisson process whose stationary mean rate
    is ``rate_rps``; bursts run ``burst_factor`` times the calm rate and
    last ``mean_burst_len`` arrivals on average)."""
    rate = float(arrivals["rate_rps"])
    if arrivals["kind"] == "poisson":
        while True:
            yield float(rng.exponential(1.0 / rate))
    if arrivals["kind"] != "bursty":
        raise ValueError(f"unknown arrivals {arrivals['kind']!r}")
    factor = float(arrivals.get("burst_factor", 4.0))
    p_burst = float(arrivals.get("p_burst", 0.25))
    q_leave = 1.0 / float(arrivals.get("mean_burst_len", 8.0))
    q_enter = q_leave * p_burst / (1.0 - p_burst)
    calm = rate * (1.0 - p_burst + p_burst / factor)
    in_burst = bool(rng.random() < p_burst)
    while True:
        yield float(rng.exponential(1.0 / (calm * factor if in_burst
                                           else calm)))
        if rng.random() < (q_leave if in_burst else q_enter):
            in_burst = not in_burst


@dataclasses.dataclass
class Record:
    """One request as its client saw it."""

    uid: int
    prompt: list[int]
    budget: int
    submitted: float
    times: list[float] = dataclasses.field(default_factory=list)
    tokens: list[int] = dataclasses.field(default_factory=list)
    finished: float | None = None
    error: str | None = None        # raised / cancelled by the server
    cut: bool = False               # cancelled by the driver at the close

    @property
    def complete(self) -> bool:
        return (self.finished is not None and self.error is None
                and not self.cut and len(self.tokens) == self.budget)

    @property
    def failed(self) -> bool:
        """Raised, cancelled by the server, or ended short of its budget."""
        if self.cut:
            return False
        if self.error is not None:
            return True
        return self.finished is not None and len(self.tokens) != self.budget


class Driver:
    """Offers one traffic mix to a started `ServingFrontend`.

    ``run(seconds)`` opens the window once every client holds a first token
    (closed loop) or once the first request has one (open loop), closes it
    ``seconds`` later, stops submitting, waits up to ``grace_s`` for the
    first token of each request submitted in the window, then cuts what is
    still in flight.  ``on_open(t_open)`` fires when the window opens.
    """

    def __init__(self, frontend, requests: Requests, *, grace_s: float = 60.0,
                 on_open=None):
        self.fe = frontend
        self.req = requests
        self.clock = frontend.clock
        self.grace_s = grace_s
        self.on_open = on_open
        self.records: list[Record] = []
        self.t_open: float | None = None
        self.t_close: float | None = None
        self._closing = False
        self._opened = asyncio.Event()
        self._streams: dict[int, object] = {}
        self._first = set()

    async def _serve(self, uid, prompt, budget, client) -> None:
        rec = Record(uid, prompt, budget, self.clock.now())
        self.records.append(rec)
        try:
            stream = self.fe.submit(prompt, uid=uid, max_new_tokens=budget)
        except Exception as e:  # noqa: BLE001 - a refused request is a result
            rec.error = f"{type(e).__name__}: {e}"
            rec.finished = self.clock.now()
            return
        self._streams[uid] = stream
        try:
            async for tok in stream:
                rec.times.append(self.clock.now())
                rec.tokens.append(int(tok))
                if client not in self._first:
                    self._first.add(client)
                    self._maybe_open()
            fr = await stream.result()
            rec.finished = self.clock.now()
            if fr.cancelled and not rec.cut:
                rec.error = "cancelled"
        except Exception as e:  # noqa: BLE001 - the server failed this one
            rec.error = f"{type(e).__name__}: {e}"
            rec.finished = self.clock.now()
        finally:
            self._streams.pop(uid, None)

    def _maybe_open(self) -> None:
        need = self.req.traffic.clients if self.req.traffic.loop == "closed" \
            else 1
        if self.t_open is None and len(self._first) >= need:
            self.t_open = self.clock.now()
            if self.on_open is not None:
                self.on_open(self.t_open)
            self._opened.set()

    def _may_submit(self) -> bool:
        return not self._closing and (self.t_close is None
                                      or self.clock.now() < self.t_close)

    async def _client(self, i: int) -> None:
        first = True
        while self._may_submit():
            uid, prompt, budget = self.req.take(i if first else None)
            await self._serve(uid, prompt, budget, i)
            first = False

    async def _open_loop(self) -> None:
        gaps = arrival_gaps(self.req.traffic.arrivals,
                            np.random.default_rng((self.req.seed, 7)))
        tasks = []
        t = self.clock.now()
        while self._may_submit():
            uid, prompt, budget = self.req.take()
            tasks.append(asyncio.ensure_future(
                self._serve(uid, prompt, budget, 0)))
            t += next(gaps)
            await self.clock.sleep(t - self.clock.now())
        await asyncio.gather(*tasks)

    async def run(self, seconds: float) -> None:
        t = self.req.traffic
        if t.loop == "closed":
            tasks = [asyncio.ensure_future(self._client(i))
                     for i in range(t.clients)]
        else:
            tasks = [asyncio.ensure_future(self._open_loop())]
        try:
            await self._opened.wait()
            self.t_close = self.t_open + seconds
            await self.clock.sleep(self.t_close - self.clock.now())
            self._closing = True
            deadline = self.clock.now() + self.grace_s
            while (self.clock.now() < deadline
                   and any(not r.times and r.finished is None
                           and r.submitted < self.t_close
                           for r in self.records)):
                await self.clock.sleep(0.005)
            for uid in list(self._streams):
                for r in self.records:
                    if r.uid == uid and r.finished is None:
                        r.cut = True
                await self.fe.cancel(uid)
            await asyncio.gather(*tasks)
        finally:
            self._closing = True
            for task in tasks:
                task.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)


@dataclasses.dataclass
class Window:
    """The end-to-end metrics of one run's window, from client records."""

    records: list[Record]
    t_open: float
    t_close: float

    def _inside(self, t: float) -> bool:
        return self.t_open <= t < self.t_close

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    def submitted(self) -> list[Record]:
        return [r for r in self.records if self._inside(r.submitted)]

    def output_tokens_per_s(self) -> float:
        n = sum(1 for r in self.records for t in r.times if self._inside(t))
        return n / self.seconds

    def itl_samples(self) -> list[float]:
        """Every gap between consecutive tokens of one request whose later
        token falls in the window, all requests pooled."""
        return [b - a for r in self.records
                for a, b in zip(r.times, r.times[1:]) if self._inside(b)]

    def ttft_samples(self) -> list[float]:
        return [r.times[0] - r.submitted for r in self.submitted() if r.times]

    def attempted(self) -> int:
        return len(self.submitted())

    def failed(self) -> int:
        return sum(1 for r in self.records if r.failed)


def p95(samples: list[float]) -> float | None:
    """95th percentile by numpy's linear rule; None without samples."""
    return float(np.percentile(samples, 95)) if samples else None
