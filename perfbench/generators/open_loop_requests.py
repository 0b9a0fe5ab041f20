"""Open loop over an ``rls_fleet`` deployment: ``ShouldRateLimit`` requests at
seeded Poisson arrivals of a fixed rate over the merged stream, each sent by
one of the deployment's nodes (drawn per request) on that node's own gRPC
channel.  A request's latency runs from the moment it was due to the moment
its answer arrived, so a stall charges every request it delays.

The nodes are the sidecars, not the program: they live in worker processes
(``python -m perfbench.generators.open_loop_requests``, which never import
JAX), each holding its share of the channels, so that sending takes no
interpreter time from the door.  ``time.monotonic_ns()`` is one clock for all
processes of a host: a worker stamps when it sent and when the answer came,
and the parent only hands out the schedule and gathers the stamps.  The
workers are started on first use, belong to the deployment from then on
(``dep.nodes``; its ``stop()`` closes them) and keep their connections across
runs, as sidecars do.
"""

from __future__ import annotations

import functools
import os
import pickle
import struct
import subprocess
import sys
from typing import List, Tuple

import numpy as np

from perfbench.generators import REPLAY_GAP_MS, Hooks, Window, now_ns, open_loop_blocks, sleep_until

# envoy.service.ratelimit.v2.RateLimitResponse.Code, and what a worker writes
# where no answer came: the sidecar's deadline passed, or another gRPC error
OK, OVER_LIMIT = 1, 2
NO_ANSWER, DEADLINE, ERROR = 0, -4, -1
METHOD = "/envoy.service.ratelimit.v2.RateLimitService/ShouldRateLimit"
#: a worker connects its channels this many at a time
CONNECT_GROUP = 128
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _varint(raw: bytes, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = raw[i]
        i += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, i
        shift += 7


def _fields(raw: bytes):
    """``(field number, value)`` of a serialized message: an int for a
    varint, bytes for a length-delimited field; other wire types do not
    occur in a ``RateLimitResponse``'s codes and are refused."""
    i = 0
    while i < len(raw):
        key, i = _varint(raw, i)
        if key & 7 == 0:
            value, i = _varint(raw, i)
        elif key & 7 == 2:
            n, i = _varint(raw, i)
            value, i = raw[i:i + n], i + n
        else:
            raise ValueError(f"wire type {key & 7} in a RateLimitResponse")
        yield key >> 3, value


def parse_response(raw: bytes) -> Tuple[int, List[int]]:
    """``(overall_code, [a code per descriptor])`` of a serialized
    ``RateLimitResponse`` (field 1, and field 1 of every field 2), read
    without the protobuf classes: a worker imports nothing of the program."""
    overall, codes = 0, []
    for number, value in _fields(raw):
        if number == 1:
            overall = value
        elif number == 2:
            codes.append(next((v for n, v in _fields(value) if n == 1), 0))
    return overall, codes


# -- the worker process -------------------------------------------------------


def _read(stream):
    head = stream.read(8)
    if len(head) < 8:
        return None
    return pickle.loads(stream.read(struct.unpack("<q", head)[0]))


def _write(stream, message) -> None:
    body = pickle.dumps(message, protocol=pickle.HIGHEST_PROTOCOL)
    stream.write(struct.pack("<q", len(body)) + body)
    stream.flush()


def _worker_run(calls, m: dict) -> dict:
    """Send ``m``'s requests when each is due and gather the answers."""
    import grpc

    due, node, which, payloads = m["due_ns"], m["node"], m["payload"], m["payloads"]
    n, t0, deadline_s = len(due), m["t0_ns"], m["deadline_s"]
    sent, done = np.zeros(n, np.int64), np.zeros(n, np.int64)

    def stamp(k, _fut):
        done[k] = now_ns()

    futs = []
    for k in range(n):
        sleep_until(t0 + due[k])
        sent[k] = now_ns()
        fut = calls[node[k]].future(payloads[which[k]], timeout=deadline_s)
        fut.add_done_callback(functools.partial(stamp, k))
        futs.append(fut)
    overall = np.full(n, NO_ANSWER, np.int8)
    codes = np.zeros((n, 2), np.int8)
    for k, fut in enumerate(futs):
        try:
            failure = fut.exception(timeout=deadline_s + 5.0)
        except grpc.FutureTimeoutError:
            fut.cancel()
            continue  # never answered: NO_ANSWER, and ``done`` stays 0
        if failure is None:
            overall[k], per = parse_response(fut.result())
            codes[k, :len(per)] = per[:2]
        else:
            overall[k] = DEADLINE if fut.code() == grpc.StatusCode.DEADLINE_EXCEEDED else ERROR
    return {"sent": sent, "done": done, "overall": overall, "codes": codes}


def worker_main() -> int:
    """Serve the parent's messages on standard input until it closes."""
    import grpc

    inp, out = sys.stdin.buffer, sys.stdout.buffer
    channels, calls = [], []
    while True:
        m = _read(inp)
        if m is None or m["op"] == "close":
            break
        if m["op"] == "connect":
            # a connection a node: channels to one address would share one
            # subchannel, so each keeps a pool of its own
            for _ in range(m["nodes"]):
                ch = grpc.insecure_channel(m["address"], options=[("grpc.use_local_subchannel_pool", 1)])
                channels.append(ch)
                calls.append(ch.unary_unary(METHOD))  # bytes in, bytes out
            # one request of a domain nobody ruled (the door answers OK and
            # asks no shard) opens the connection, as a sidecar's first did
            for i in range(0, len(calls), CONNECT_GROUP):
                group = [c.future(m["hello"], timeout=30.0, wait_for_ready=True)
                         for c in calls[i:i + CONNECT_GROUP]]
                for fut in group:
                    fut.result()
            _write(out, {"connected": len(calls)})
        elif m["op"] == "run":
            _write(out, _worker_run(calls, m))
    for ch in channels:
        ch.close()
    return 0


# -- the parent's side ----------------------------------------------------------


class Nodes:
    """The deployment's sidecars: ``workers`` processes, node ``i`` on worker
    ``i % workers``, every node connected to the door before this returns."""

    def __init__(self, address: str, n_nodes: int, workers: int, hello: bytes):
        self.n_nodes, self.workers = n_nodes, workers
        self._order = []  # which requests of the last ``send`` went to each worker
        env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
        # a worker imports no JAX; were that to change, it must not reach for the chip
        env.update(PYTHONPATH=ROOT, JAX_PLATFORMS="cpu", PYTHONDONTWRITEBYTECODE="1")
        self.procs = []
        try:
            for _ in range(workers):
                self.procs.append(subprocess.Popen(
                    [sys.executable, "-m", "perfbench.generators.open_loop_requests"],
                    cwd=ROOT, env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE))
            for w, p in enumerate(self.procs):
                held = len(range(w, n_nodes, workers))
                _write(p.stdin, {"op": "connect", "address": address, "nodes": held, "hello": hello})
            for w, p in enumerate(self.procs):
                if self._answer(w)["connected"] != len(range(w, n_nodes, workers)):
                    raise RuntimeError(f"node worker {w} did not connect its nodes")
        except BaseException:
            self.close()
            raise

    def _answer(self, w: int) -> dict:
        m = _read(self.procs[w].stdout)
        if m is None:
            raise RuntimeError(f"node worker {w} ended (exit code {self.procs[w].poll()})")
        return m

    def send(self, t0_ns: int, due_ns, node, payload, payloads, deadline_s: float) -> None:
        """Hand every worker the requests of its nodes."""
        self._order = []
        for w, p in enumerate(self.procs):
            mine = np.flatnonzero(node % self.workers == w)
            self._order.append(mine)
            _write(p.stdin, {"op": "run", "t0_ns": int(t0_ns), "due_ns": due_ns[mine],
                             "node": node[mine] // self.workers, "payload": payload[mine],
                             "payloads": payloads, "deadline_s": deadline_s})

    def gather(self) -> dict:
        """The workers' stamps and answers, in the order the requests were handed in."""
        n = sum(len(mine) for mine in self._order)
        out = {"sent": np.zeros(n, np.int64), "done": np.zeros(n, np.int64),
               "overall": np.zeros(n, np.int8), "codes": np.zeros((n, 2), np.int8)}
        for w, mine in enumerate(self._order):
            got = self._answer(w)
            for key, arr in out.items():
                arr[mine] = got[key]
        return out

    def close(self) -> None:
        for p in self.procs:
            try:
                _write(p.stdin, {"op": "close"})
                p.stdin.close()
            except (OSError, ValueError):
                pass  # the worker is gone already
        for p in self.procs:
            try:
                p.wait(timeout=10.0)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
            p.stdout.close()
        self.procs = []


def nodes_of(dep, params: dict) -> Nodes:
    if dep.nodes is None:
        hello = dep.request_bytes(-1, [0])  # domain "mesh--1": no such rule
        dep.nodes = Nodes(dep.address, dep.config["nodes"]["n"], params["node_workers"], hello)
    return dep.nodes


def schedule(params: dict, seed: int, seconds: float) -> np.ndarray:
    """Due times in ns from the generator's start: ``open_loop_blocks``'s
    schedule (the same multiset of gaps for every seed, in another order),
    a request a block."""
    return open_loop_blocks.schedule(
        dict(params, rate_items_per_s=params["rate_requests_per_s"], block_items=1), seed, seconds)


def traffic(dep, rng, n: int):
    """``n`` requests from the configuration's shapes: ``(node, descriptors
    [n, 2])``, the second descriptor -1 where the request carries one.  The
    node is drawn evenly (every node offers the same rate), the first
    descriptor Zipf over the services of the node's domain, and one request
    in ``two_descriptor_share`` carries a second, different one."""
    from perfbench.deployments.rls_fleet import service_probs

    cfg = dep.config
    q = service_probs(cfg)
    node = rng.integers(0, cfg["nodes"]["n"], n)
    first = rng.choice(len(q), n, p=q)
    second = np.full(n, -1)
    again = np.flatnonzero(rng.random(n) < cfg["nodes"]["two_descriptor_share"])
    while len(again):
        second[again] = rng.choice(len(q), len(again), p=q)
        again = again[second[again] == first[again]]
    base = dep.node_domain[node] * len(q)
    desc = np.stack([base + first, np.where(second >= 0, base + second, -1)], axis=1)
    return node, desc


def payloads_of(dep, desc: np.ndarray):
    """``(index per request, the distinct serialized requests)``."""
    services = dep.config["rules"]["services"]
    uniq, index = np.unique(desc, axis=0, return_inverse=True)
    raws = [dep.request_bytes(int(a) // services, [int(d) % services for d in (a, b) if d >= 0])
            for a, b in uniq]
    return index.reshape(-1), raws


def admitted_per_descriptor(dep, desc: np.ndarray, codes: np.ndarray) -> np.ndarray:
    """Hits answered OK per descriptor (``hits_addend`` units each)."""
    ok = (codes == OK) & (desc >= 0)
    return np.bincount(desc[ok], minlength=len(dep.counts)) * dep.config["nodes"]["hits_addend"]


def run(dep, params: dict, seed: int, seconds: float, hooks: Hooks) -> Window:
    """Pre-roll, window, post-roll, one schedule.  Where the pre-roll is
    longer than ``heal_at_s``, its first ``heal_at_s`` seconds go out on
    their own, every answer is waited for, and a shard that they left degraded
    (the first requests after set-up and, in a traced run, after the
    profiler's start meet stalls longer than the ring's patience) is healed
    (``dep.settle()``: nothing in a sound run) before the rest follows: the
    window is a fleet's that is whole, or the check says it was not.  The
    program's counters are read when the window opens and when the last answer
    has come, so the account is the window's and the post-roll's, and the
    requests in flight while the first reading was taken are counted apart."""
    nodes = nodes_of(dep, params)
    due = schedule(params, seed, seconds)
    n = len(due)
    node, desc = traffic(dep, np.random.default_rng(seed + 1), n)
    which, raws = payloads_of(dep, desc)
    units = dep.config["nodes"]["hits_addend"]
    deadline_s = dep.config["nodes"]["deadline_ms"] / 1e3
    lead = int(params["lead_s"] * 1e9)
    open_rel = int(params["preroll_s"] * 1e9)
    close_rel = open_rel + int(seconds * 1e9)
    heal_rel = int(params.get("heal_at_s", 0.0) * 1e9)
    in_win = (due >= open_rel) & (due < close_rel)
    hooks.progress = dep.answered
    start = np.zeros(n, np.int64)  # the instant each request's due time counts from
    got = {"sent": np.zeros(n, np.int64), "done": np.zeros(n, np.int64),
           "overall": np.zeros(n, np.int8), "codes": np.zeros((n, 2), np.int8)}

    def send(mine, t0):
        start[mine] = t0
        nodes.send(t0, due[mine], node[mine], which[mine], raws, deadline_s)

    def gather(mine):
        for key, arr in nodes.gather().items():
            got[key][mine] = arr

    healed, rest, skipped = [], np.ones(n, bool), 0
    if 0 < heal_rel < open_rel:
        rest = due >= heal_rel
        send(~rest, now_ns() + lead)
        gather(~rest)
        healed, skipped = dep.settle(), heal_rel
    t0 = now_ns() + lead - skipped  # the rest keeps its due times, less what has been sent
    send(rest, t0)
    sleep_until(t0 + open_rel)
    hooks.opened()
    read0 = now_ns()
    before, degraded_at_open = dep.counters(), dep.degraded()
    read1 = now_ns()
    sleep_until(t0 + close_rel)
    hooks.closed()
    degraded_at_close = dep.degraded()
    gather(rest)
    sent, done, overall, codes = got["sent"], got["done"], got["overall"], got["codes"]
    moved = {k: v - before[k] for k, v in dep.counters().items()}

    answered = (overall == OK) | (overall == OVER_LIMIT)
    lat_ms = (done - (start + due)) / 1e6
    good = in_win & answered
    late = in_win & (overall == DEADLINE)
    vis = answered & (done >= t0 + open_rel) & (done < t0 + close_rel)
    # against the counters' two readings: sent after the first, a request was
    # decided between them if it was decided at all; sent before it and not
    # answered before it, it may have been decided on either side
    after_open = sent > read1
    across_open = (sent > 0) & ~after_open & ((done == 0) | (done >= read0))

    def hits(mask) -> int:
        return int((desc[mask] >= 0).sum()) * units

    def pending(at_ns: int) -> int:  # sent and not yet answered at that instant
        return int(((sent > 0) & (sent <= at_ns)).sum() - ((done > 0) & (done <= at_ns)).sum())

    return Window(
        seconds=seconds,
        open_ns=t0 + open_rel,
        close_ns=t0 + close_rel,
        attempted=int(in_win.sum()),
        failed=int((in_win & ~answered).sum()),
        latency_ms=lat_ms[good],
        due_ns=(start + due)[good],
        visible_items=int(vis.sum()),
        late_ms=(sent - (start + due))[in_win] / 1e6,
        passes=admitted_per_descriptor(dep, desc, codes),
        codes={int(c): int((overall == c).sum()) for c in np.unique(overall)},
        unresolved=int((overall == NO_ANSWER).sum()),
        span_s=float((done.max() - sent.min()) / 1e9),
        late=int(late.sum()),
        extra={
            "pending_mid": pending(t0 + (open_rel + close_rel) // 2),
            "pending_end": pending(t0 + close_rel),
            "offered_requests_per_s": n / (params["preroll_s"] + seconds + params["postroll_s"]),
            # the account between the counters' readings (``moved.*`` below)
            "hits_answered": hits(after_open & answered),
            "hits_unanswered": hits(after_open & ~answered),
            "hits_across_open": hits(across_open),
            "degraded_at_open": degraded_at_open,
            "degraded_at_close": degraded_at_close,
            "healed_in_preroll": healed,
            "two_descriptor_requests": int((desc[:, 1] >= 0).sum()),
            "errors": int((in_win & (overall == ERROR)).sum()),
            # answers past the sidecar's deadline over the whole run
            "deadline_answers": int((overall == DEADLINE).sum()),
            # Envoy's own default timeout is 20 ms: the share of answers a
            # sidecar at that default would have given up on
            "over_20ms_share": float((lat_ms[good] > 20.0).mean()) if good.any() else 0.0,
            "worst_latency_ms": float(lat_ms[in_win & (done > 0)].max(initial=0.0)),
            **{f"moved.{k}": v for k, v in moved.items()},
        },
    )


def replay(dep, params: dict, seed: int) -> list:
    """Drive ``replay.steps`` steps of the cell's traffic through the same
    door, nodes and compiled programs at stated instants: the shards' clocks
    are held at a step's time, its requests go out together, and the next
    step waits for every answer.  Nothing here depends on the real clock but
    patience, the sidecars' and the ring's (``replay.deadline_ms`` both).  Returns
    ``[(t_ms, descriptors [n, 2], codes [n, 2], overall [n]), ...]``."""
    rp = params["replay"]
    nodes = nodes_of(dep, params)
    rng = np.random.default_rng(seed + 2)
    dep.settle()
    dep.patience(rp["deadline_ms"])
    t = dep.now_ms() + REPLAY_GAP_MS
    steps = []
    try:
        for i in range(rp["steps"]):
            n = rp["requests_per_step"][i % len(rp["requests_per_step"])]
            node, desc = traffic(dep, rng, n)
            which, raws = payloads_of(dep, desc)
            dep.hold_clocks(t)
            nodes.send(now_ns(), np.zeros(n, np.int64), node, which, raws, rp["deadline_ms"] / 1e3)
            got = nodes.gather()
            steps.append((t, desc, got["codes"], got["overall"]))
            t += rp["step_ms"][i % len(rp["step_ms"])]
    finally:
        dep.patience()
    return steps


if __name__ == "__main__":
    sys.exit(worker_main())
