"""Fleet worker: one server's slice of the event engine, crash-consistent.

A worker owns row ``p`` of the protocol: it folds its dispatched cohort's
client updates into a :class:`~repro.core.events.buffer.
BufferedServerState`-shaped numpy buffer, flushes (announces a protected
psi and charges its privacy ledger) when the buffer fills, and replies to
the coordinator.  Everything it computes is a pure function of
``(seeds, server, tick)`` — client shards, cohort updates and the flush
noise are all derived from counter-based generators — which is what makes
crash recovery *exact*: a restarted worker that replays a tick recomputes
bit-identical results.

Crash consistency is write-ahead checkpointing through
:mod:`repro.checkpoint.io` (crash-atomic ``os.replace`` publish): every
``ckpt_every`` ticks the worker persists ``(params, buffer state,
version, tick_done, accountant q-ledger, last reply)`` BEFORE sending its
reply.  Combined with the dedup keys this yields exactly-once folding
across kills:

* killed before the checkpoint — the coordinator never saw the reply; the
  re-dispatched tick is recomputed deterministically, same fold;
* killed between checkpoint and send — the restored worker sees the
  re-dispatched tick is ``<= tick_done`` and resends the CHECKPOINTED
  reply without re-folding (idempotent replay);
* duplicate delivery (a retried dispatch whose original did arrive) hits
  the same ``tick <= tick_done`` guard.

At ``ckpt_every = 1`` recovery loses nothing; at larger cadences at most
``ckpt_every - 1`` ticks of buffer fold are recomputed-or-lost, as
documented in the ``fleet`` spec grammar.

The module is import-light on purpose (numpy + checkpoint io): it is the
entry point of spawned worker processes (filelog / socket transports) and
of in-process worker threads (the tier-1-safe ``inproc`` realization).
"""
from __future__ import annotations

import json
import os
import threading
import time
from dataclasses import asdict, dataclass
from typing import Optional

import numpy as np

from repro.core.fleet.namebook import COORDINATOR, worker_name
from repro.core.fleet.spec import FleetSpec, parse_fleet_spec
from repro.core.fleet.transport import (Message, Transport, make_transport,
                                        pack_array, send_with_retry,
                                        unpack_array)

_SHARD_TAG = 0xDA7A     # client data stream
_NOISE_TAG = 0x4015E    # flush (release) noise stream


@dataclass(frozen=True)
class FleetProblem:
    """The fleet's shared protocol constants (picklable; rides the spawn
    args of every worker process).  Mirrors the Section V logistic setup
    at fleet scale: client ``(p, k)``'s shard is a pure function of
    ``(data_seed, p, k)``."""
    P: int = 4
    K: int = 20            # clients per server
    n: int = 20            # samples per client
    dim: int = 2
    rho: float = 0.01
    mu: float = 0.05
    grad_bound: float = 5.0
    buffer: int = 8        # arrivals per flush (AsyncSpec.buffer analogue)
    events: int = 4        # cohort size per dispatch tick
    sigma_g: float = 0.0   # flush Laplace noise std (0 = noiseless)
    data_seed: int = 0
    seed: int = 0          # protocol seed (noise stream)

    def to_dict(self) -> dict:
        return asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "FleetProblem":
        return cls(**d)


def client_shard(prob: FleetProblem, p: int, k: int):
    """(h [n, dim], gamma [n]) of client ``(p, k)`` — pure in
    ``(data_seed, p, k)`` (the population-engine sharding discipline)."""
    rng = np.random.default_rng((_SHARD_TAG, prob.data_seed, p, k))
    gamma = np.where(rng.random(prob.n) < 0.5, -1.0, 1.0)
    sigma_h = 0.5 + rng.random()            # heterogeneous client noise
    h = gamma[:, None] + rng.normal(0.0, sigma_h, (prob.n, prob.dim))
    return h, gamma


def logistic_grad(w: np.ndarray, h: np.ndarray, gamma: np.ndarray,
                  rho: float) -> np.ndarray:
    """grad of the rho-regularized mean logistic loss (numpy twin of
    ``simulate.logistic_loss``)."""
    margins = gamma * (h @ w)
    sig = 1.0 / (1.0 + np.exp(np.clip(margins, -50.0, 50.0)))
    return -(gamma * sig) @ h / len(gamma) + rho * w


def clip_to_bound(g: np.ndarray, bound: float) -> np.ndarray:
    if bound <= 0:
        return g
    nrm = float(np.linalg.norm(g))
    return g * min(1.0, bound / max(nrm, 1e-12))


def client_update(prob: FleetProblem, w: np.ndarray, p: int, k: int
                  ) -> np.ndarray:
    """One client's eq.-6 step against the dispatched model."""
    h, gamma = client_shard(prob, p, k)
    grad = clip_to_bound(logistic_grad(w, h, gamma, prob.rho),
                         prob.grad_bound)
    return w - prob.mu * grad


def flush_noise(prob: FleetProblem, p: int, version: int) -> np.ndarray:
    """Release ``version``'s Laplace draw, ``Lap(0, sigma_g/sqrt 2)`` per
    coordinate (std sigma_g, the homomorphic-mechanism convention) — pure
    in ``(seed, p, version)`` so a replayed flush re-draws identically."""
    if prob.sigma_g <= 0:
        return np.zeros(prob.dim)
    rng = np.random.default_rng((_NOISE_TAG, prob.seed, p, version))
    return rng.laplace(0.0, prob.sigma_g / np.sqrt(2.0), prob.dim)


# ---------------------------------------------------------------------------
# checkpoint pytree (variable-shape q ledger => manifest-driven "like")
# ---------------------------------------------------------------------------


def _state_tree(state: dict) -> dict:
    return {k: np.asarray(v) for k, v in state.items()}


def load_worker_checkpoint(path: str) -> Optional[dict]:
    """Restore a worker state dict, or None when no checkpoint exists.

    The state carries a variable-length ``q_history`` ledger, so the
    ``like`` tree :func:`repro.checkpoint.io.load_checkpoint` validates
    against is built from the manifest's own recorded shapes/dtypes."""
    manifest_path = os.path.join(path, "manifest.json")
    if not os.path.exists(manifest_path):
        return None
    from repro.checkpoint.io import load_checkpoint
    with open(manifest_path) as f:
        manifest = json.load(f)
    like = {k: np.zeros(manifest["shapes"][k],
                        np.dtype(manifest["dtypes"][k]))
            for k in manifest["keys"]}
    tree, _ = load_checkpoint(path, like)
    return {k: np.asarray(v) for k, v in tree.items()}


class FleetWorker:
    """The worker event loop (one per server row).

    Runs until a ``stop`` message (graceful: final checkpoint + ``bye``)
    or until ``kill_flag`` is set (the inproc chaos realization of
    SIGKILL: the loop aborts WITHOUT checkpointing, exactly like a killed
    process).  Process realizations are killed for real — see
    :mod:`repro.core.fleet.coordinator`.
    """

    def __init__(self, p: int, prob: FleetProblem, spec: FleetSpec,
                 transport: Transport, ckpt_dir: str):
        self.p = p
        self.prob = prob
        self.spec = spec
        self.transport = transport
        self.ckpt_dir = ckpt_dir
        self.name = worker_name(p)
        self.kill_flag = threading.Event()

        restored = load_worker_checkpoint(ckpt_dir)
        if restored is not None:
            self.params = restored["params"]
            self.buf_sum = restored["buf_sum"]
            self.buf_wsum = float(restored["buf_wsum"])
            self.buf_n = int(restored["buf_n"])
            self.version = int(restored["version"])
            self.psi_cache = restored["psi_cache"]
            self.tick_done = int(restored["tick_done"])
            self.q_history = [float(v) for v in
                              np.atleast_1d(restored["q_history"])
                              [:self.version]]
            self.last_reply = {
                "tick": int(restored["last_tick"]),
                "psi": pack_array(restored["last_psi"]),
                "flushed": int(restored["last_flushed"]),
                "q": float(restored["last_q"]),
            }
        else:
            self.params = np.zeros(prob.dim)
            self.buf_sum = np.zeros(prob.dim)
            self.buf_wsum = 0.0
            self.buf_n = 0
            self.version = 0
            self.psi_cache = np.zeros(prob.dim)
            self.tick_done = -1
            self.q_history: list = []
            self.last_reply: Optional[dict] = None

    # ------------------------------------------------------------ protocol

    def compute_tick(self, tick: int, w: np.ndarray, cohort: list) -> dict:
        """Fold the dispatched cohort, maybe flush; returns the reply
        payload.  Deterministic in ``(prob, tick, w, cohort)``."""
        self.params = np.asarray(w, np.float64)
        updates = [client_update(self.prob, self.params, self.p, int(k))
                   for k in cohort]
        n = len(updates)
        if n:
            # age-0 fold: every staleness weight is 1, mass == count
            self.buf_sum = self.buf_sum + np.sum(updates, axis=0)
            self.buf_wsum += float(n)
            self.buf_n += n
        flushed = self.buf_n >= self.prob.buffer
        if flushed:
            psi = self.buf_sum / max(self.buf_wsum, 1e-12)
            self.version += 1
            psi = psi + flush_noise(self.prob, self.p, self.version)
            q = min(1.0, self.buf_n / self.prob.K)
            self.q_history.append(q)
            self.buf_sum = np.zeros(self.prob.dim)
            self.buf_wsum = 0.0
            self.buf_n = 0
            self.psi_cache = psi
        else:
            psi = self.psi_cache
            q = 0.0
        self.tick_done = tick
        return {"tick": tick, "psi": pack_array(psi),
                "flushed": int(flushed), "q": q}

    def checkpoint(self) -> None:
        """Write-ahead checkpoint (crash-atomic via checkpoint/io.py)."""
        from repro.checkpoint.io import save_checkpoint
        last = self.last_reply or {"tick": -1,
                                   "psi": pack_array(self.psi_cache),
                                   "flushed": 0, "q": 0.0}
        save_checkpoint(self.ckpt_dir, _state_tree({
            "params": self.params,
            "buf_sum": self.buf_sum,
            "buf_wsum": np.float64(self.buf_wsum),
            "buf_n": np.int64(self.buf_n),
            "version": np.int64(self.version),
            "psi_cache": self.psi_cache,
            "tick_done": np.int64(self.tick_done),
            "q_history": np.asarray(self.q_history, np.float64),
            "last_tick": np.int64(last["tick"]),
            "last_psi": unpack_array(last["psi"]),
            "last_flushed": np.int64(last["flushed"]),
            "last_q": np.float64(last["q"]),
        }), step=self.tick_done)

    def _reply(self, payload: dict) -> None:
        send_with_retry(self.transport, COORDINATOR,
                        Message("psi", self.name, self.version, payload),
                        self.spec)

    # ------------------------------------------------------------ main loop

    def run(self) -> None:
        hello = Message("hello", self.name, self.version, {
            "tick_done": self.tick_done, "pid": os.getpid(),
            "address": list(getattr(self.transport, "address", ()) or []),
        })
        send_with_retry(self.transport, COORDINATOR, hello, self.spec)
        stop_beats = threading.Event()
        beats = threading.Thread(target=self._heartbeat_loop,
                                 args=(stop_beats,), daemon=True,
                                 name=f"fleet-beats-{self.name}")
        beats.start()
        try:
            while not self.kill_flag.is_set():
                msg = self.transport.recv(timeout=min(self.spec.heartbeat,
                                                      0.1))
                if msg is None:
                    continue
                if msg.kind == "stop":
                    self.checkpoint()
                    send_with_retry(
                        self.transport, COORDINATOR,
                        Message("bye", self.name, self.version,
                                {"q_history": list(self.q_history)}),
                        self.spec)
                    return
                if msg.kind != "cohort":
                    continue
                tick = int(msg.payload["tick"])
                if tick <= self.tick_done:
                    # duplicate / replayed dispatch: resend the stored
                    # reply, fold NOTHING (exactly-once effect)
                    if self.last_reply is not None \
                            and self.last_reply["tick"] == tick:
                        self._reply(self.last_reply)
                    continue
                payload = self.compute_tick(
                    tick, unpack_array(msg.payload["w"]),
                    msg.payload["cohort"])
                self.last_reply = payload
                if tick % self.spec.ckpt_every == 0:
                    self.checkpoint()     # WRITE-AHEAD: persist, THEN reply
                if self.kill_flag.is_set():
                    return                # killed between checkpoint & send
                self._reply(payload)
        finally:
            stop_beats.set()
            self.transport.close()

    def _heartbeat_loop(self, stop: threading.Event) -> None:
        while not stop.wait(self.spec.heartbeat):
            if self.kill_flag.is_set():
                return
            try:
                self.transport.send(
                    COORDINATOR,
                    Message("heartbeat", self.name, self.version,
                            {"tick_done": self.tick_done}))
            except Exception:
                pass                      # missed beats ARE the signal


def worker_process_main(p: int, prob_dict: dict, spec_str: str,
                        ckpt_dir: str, transport_kind: str,
                        root: Optional[str],
                        coordinator_addr: Optional[tuple]) -> None:
    """Spawned-process entry point (filelog / socket transports).

    Arguments are plain picklable values; the transport is rebuilt inside
    the child.  In socket mode the worker binds an ephemeral port and
    reports its address in the hello — the coordinator's namebook is the
    only place addresses accumulate.

    The worker is numpy; it is pinned to the CPU backend before anything
    can create another, so it never contends with its parent for a chip.
    """
    import jax
    jax.config.update("jax_platforms", "cpu")
    prob = FleetProblem.from_dict(prob_dict)
    spec = parse_fleet_spec(spec_str)
    name = worker_name(p)
    if transport_kind == "filelog":
        transport = make_transport(spec, name, root=root)
    else:
        addresses = {} if coordinator_addr is None else \
            {COORDINATOR: tuple(coordinator_addr)}
        transport = make_transport(spec, name, addresses=addresses)
    FleetWorker(p, prob, spec, transport, ckpt_dir).run()
