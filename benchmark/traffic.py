"""One general generator of query traffic, driven by a file of parameters.

A traffic mix is ``benchmark/traffic/<name>.json``; a later PR adds a mix by
adding a file. The generator knows nothing of any mix by name. Every mix is
a closed loop with no think time (each client sends its next query when the
last one has been answered) over live ingest. Parameters:

- ``clients``: how many.
- ``tenant``: null, or a template with ``{client}`` (sent as X-Filo-Tenant).
- ``queries``: [{"promql", "ref": {...}}] — the text sent, and what the
  deployment's data module (``benchmark/data/``) evaluates for it: ``ref``
  goes through the harness unread, its keys are that module's.
- ``ranges``: [{"range_s", "step_s", "end_back_s": [..]}] — a query covers
  ``range_s`` ending ``end_back_s`` before the head of the filled history.
- ``order``:
  - "shared_deck": the cards are queries x ranges x end_back_s; one deck is
    shared by all clients, each takes the next card, and the deck is dealt
    anew when it runs out. The deck goes through the query texts in turn
    (text 0, 1, 2, ... again and again) and the seed shuffles which window
    each text gets when. So every seed issues the same set of work, and
    the texts — whose costs differ fiftyfold — arrive in the same rhythm.
    Every query of a (promql, step) key starts 1009 ms (a prime), in whole
    milliseconds, further on in phase than the last, from a seeded start:
    the program's fragment cache keeps ONE entry per key and can only
    extend it when ``(start - entry.start) % step == 0``, and its result
    cache keys on the exact range, so no query can be answered from what
    its last ``step_ms`` predecessors left.
  - "panels": each client owns a dashboard and issues ``queries`` in order
    over ranges[0]; one pass is a refresh, and ``slide`` moves the range's
    end between refreshes.
- ``slide``: {"lap_steps": n, "start_back": [one per client]} — a
  dashboard's refresh ends ``pos`` steps (and its phase) before the head;
  each refresh is one step further on (pos - 1), and after the refresh at
  the head (pos 0) the dashboard goes back to pos n - 1 with its phase
  1009 ms further on: a lap. Within a lap every refresh shares all but one
  step with the one before (the fragment cache's case); the first refresh
  of a lap is off every cached grid and range, so it executes in full. The
  share of either is fixed by ``lap_steps`` — one full execution in n —
  however fast the system answers, and no range ever comes twice, so the
  result cache never answers. The seed deals ``start_back`` out among the
  clients and draws each dashboard's first phase: every seed runs the same
  set of work.
- ``warmup``: what set-up sends before the window so that every compiled
  shape and every cache the mix relies on exists: "deck" = one query per
  card; {"refreshes": r, "shapes_back": [..]} = r refreshes by every
  client (the window goes on from where they end), and one dashboard more,
  a tenant that the window never uses, that refreshes at each position of
  ``shapes_back`` in turn (the compiled shapes of a lap: programs are
  shared between tenants).

The head is the stamp of the first live scrape, which set-up has seen land;
no query reaches past it, so every answer is a function of the seed.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
PHASE_STRIDE_MS = 1009


@dataclasses.dataclass(frozen=True)
class Request:
    client: int
    qi: int                      # index into queries
    promql: str
    start_ms: int
    end_ms: int
    step_ms: int
    tenant: str | None

    def out_ts(self) -> np.ndarray:
        return np.arange(self.start_ms, self.end_ms + 1, self.step_ms,
                         dtype=np.int64)


def load(name: str, home: str = HERE) -> dict:
    with open(os.path.join(home, "traffic", f"{name}.json")) as f:
        return json.load(f)


class Generator:
    def __init__(self, mix: dict, seed: int, head_ms: int):
        self.mix = mix
        self.head_ms = int(head_ms)
        self.clients = int(mix["clients"])
        self.queries = mix["queries"]
        self.ranges = mix["ranges"]
        self.order = mix["order"]
        self._lock = threading.Lock()
        self._rng = np.random.default_rng([int(seed) & 0xFFFFFFFF,
                                           int(seed) >> 32, 0x7AFF1C])
        self._phase: dict[tuple, int] = {}
        if self.order == "shared_deck":
            self.cards = [(qi, ri, back)
                          for qi in range(len(self.queries))
                          for ri, r in enumerate(self.ranges)
                          for back in r["end_back_s"]]
            self._deck: list = []
        elif self.order == "panels":
            sl = mix["slide"]
            self._lap = int(sl["lap_steps"])
            starts = [int(p) for p in sl["start_back"]]
            if len(starts) != self.clients or max(starts) >= self._lap:
                raise ValueError("slide.start_back: one position inside the "
                                 "lap for every client")
            step_ms = 1000 * int(self.ranges[0]["step_s"])
            # one dashboard more than clients: the warm-up's own
            self._pos = [starts[i] for i in self._rng.permutation(self.clients)
                         ] + [0]                           # steps before head
            self._lap_phase = [int(self._rng.integers(0, step_ms))
                               for _ in range(self.clients + 1)]
            self._panel = [0] * (self.clients + 1)
        else:
            raise ValueError(f"unknown order {self.order!r}")

    def _deal(self) -> list:
        """Texts in turn; each text's windows in a seeded order."""
        windows = [(ri, back) for ri, r in enumerate(self.ranges)
                   for back in r["end_back_s"]]
        per_text = [[windows[i] for i in self._rng.permutation(len(windows))]
                    for _ in self.queries]
        return [(qi, *per_text[qi][k]) for k in range(len(windows))
                for qi in range(len(self.queries))]

    def tenant_of(self, client: int) -> str | None:
        t = self.mix.get("tenant")
        return t.format(client=client) if t else None

    def _phase_ms(self, qi: int, step_ms: int) -> int:
        key = (qi, step_ms)
        p = self._phase.get(key)
        if p is None:
            p = int(self._rng.integers(0, step_ms))
        self._phase[key] = (p + PHASE_STRIDE_MS) % step_ms
        return p

    def _card_request(self, client: int, card) -> Request:
        qi, ri, back = card
        r = self.ranges[ri]
        end = (self.head_ms - 1000 * int(back)
               - self._phase_ms(qi, 1000 * r["step_s"]))
        return Request(client, qi, self.queries[qi]["promql"],
                       end - 1000 * r["range_s"], end, 1000 * r["step_s"],
                       self.tenant_of(client))

    def _panel_request(self, client: int) -> Request:
        r = self.ranges[0]
        step_ms = 1000 * int(r["step_s"])
        qi = self._panel[client]
        end = (self.head_ms - step_ms * self._pos[client]
               - self._lap_phase[client])
        req = Request(client, qi, self.queries[qi]["promql"],
                      end - 1000 * r["range_s"], end, step_ms,
                      self.tenant_of(client))
        self._panel[client] = (qi + 1) % len(self.queries)
        if self._panel[client] == 0:            # the next refresh: one step on
            self._pos[client] -= 1
            if self._pos[client] < 0:           # past the head: a new lap
                self._pos[client] = self._lap - 1
                self._lap_phase[client] = (self._lap_phase[client]
                                           + PHASE_STRIDE_MS) % step_ms
        return req

    def next(self, client: int) -> Request:
        with self._lock:
            if self.order == "shared_deck":
                if not self._deck:
                    self._deck = self._deal()
                return self._card_request(client, self._deck.pop(0))
            return self._panel_request(client)

    def warmup(self) -> list[Request]:
        w = self.mix["warmup"]
        if w == "deck":
            with self._lock:
                return [self._card_request(0, c) for c in self.cards]
        panels = len(self.queries)
        out = []
        with self._lock:
            for c in range(self.clients):
                out += [self._panel_request(c)
                        for _ in range(int(w["refreshes"]) * panels)]
            spare = self.clients
            for pos in w["shapes_back"]:
                self._pos[spare] = int(pos)
                out += [self._panel_request(spare) for _ in range(panels)]
        return out
