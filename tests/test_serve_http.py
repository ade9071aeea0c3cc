"""HTTP front-door conformance + end-to-end serving tests (PR 8).

Two tiers:

* **Stub conformance** (default, no JAX compile): the front door over
  ``serving_stub.StubScheduler`` — SSE framing (monotone event ids,
  heartbeats under silence, terminal event carrying finish_reason +
  usage), backpressure 429 + ``Retry-After`` BEFORE admission, tenant
  rate-limit 429, disconnect-mid-stream reclaiming the slot and its paged
  blocks within one segment (asserted via allocator stats), graceful
  drain, and protocol errors (400/404/405/413/503).
* **Real engine** (``-m http``, its own CI shard): for a fixed arrival
  order, greedy outputs through the HTTP path are bit-identical to the
  offline ``ContinuousScheduler`` drain, and the chaos suite
  (cancel/exhaust/slot-fail) runs underneath concurrent HTTP clients
  with survivors unchanged — failing seeds printed as in
  ``test_serve_robust.py``.

No external HTTP library: clients use the stdlib asyncio helpers shipped
with ``repro.serve.http``; tests run under plain ``asyncio.run`` (the
environment has no pytest-asyncio).
"""
from __future__ import annotations

import asyncio

import numpy as np
import pytest

from serving_stub import StubScheduler, drain_offline, stub_token

from repro.serve.http import (FrontDoor, HttpConfig, generate, http_get,
                              open_generate, read_sse_event)
from repro.serve.policy import TenantPolicy, TenantSpec
from repro.serve.request import SubmitRequest

HOST = "127.0.0.1"


def _run(coro):
    return asyncio.run(coro)


async def _with_fd(sched, cfg, fn):
    """start → fn(front_door) → graceful stop, whatever fn does."""
    fd = FrontDoor(sched, cfg)
    await fd.start()
    try:
        return await fn(fd)
    finally:
        await fd.stop()


def _gen_payload(prompt, max_new, **kw):
    return {"prompt": [int(t) for t in prompt], "max_new_tokens": max_new,
            **kw}


# ------------------------------------------------------------ SSE framing


def test_sse_framing_and_terminal_event():
    """Token events carry monotone ids from 0; the terminal event carries
    finish_reason, usage, and the full token list; tokens match the stub
    oracle exactly."""
    async def fn(fd):
        return await generate(HOST, fd.port, _gen_payload([5, 9], 6))

    out = _run(_with_fd(StubScheduler(), HttpConfig(), fn))
    assert out["status"] == 200
    toks = [e["data"]["token"] for e in out["events"]
            if e.get("event") == "token"]
    assert toks == [stub_token([5, 9], i) for i in range(6)]
    ids = [e["id"] for e in out["events"] if "id" in e]
    assert ids == list(range(len(ids))), ids  # monotone from 0, no gaps
    done = out["events"][-1]
    assert done["event"] == "done" and done["id"] == 6
    body = done["data"]
    assert body["finish_reason"] == "length" and body["state"] == "finished"
    assert body["usage"] == {"prompt_tokens": 2, "completion_tokens": 6}
    assert body["tokens"] == toks


def test_eos_finish_reason_stop():
    """Hitting the stub's eos id retires with finish_reason='stop' short of
    the budget, and usage counts only the emitted tokens."""
    prompt = [11, 4]
    eos = stub_token(prompt, 2)
    async def fn(fd):
        return await generate(HOST, fd.port, _gen_payload(prompt, 10))

    out = _run(_with_fd(StubScheduler(eos_id=eos), HttpConfig(), fn))
    body = out["body"]
    assert body["finish_reason"] == "stop"
    assert body["tokens"][-1] == eos
    assert body["usage"]["completion_tokens"] == 3


def test_non_streaming_single_json_response():
    async def fn(fd):
        return await generate(
            HOST, fd.port, _gen_payload([3, 4], 3, stream=False))

    out = _run(_with_fd(StubScheduler(), HttpConfig(), fn))
    assert out["status"] == 200 and out["events"] == []
    assert out["body"]["tokens"] == [stub_token([3, 4], i) for i in range(3)]
    assert out["body"]["finish_reason"] == "length"


def test_heartbeats_under_silence():
    """A slow segment emits SSE heartbeats so idle connections stay live."""
    sched = StubScheduler(steps_per_segment=1, segment_delay_s=0.3)
    async def fn(fd):
        return await generate(HOST, fd.port, _gen_payload([2, 2], 2))

    out = _run(_with_fd(sched, HttpConfig(heartbeat_s=0.05), fn))
    kinds = [e.get("event") for e in out["events"]]
    assert kinds.count("heartbeat") >= 1, kinds
    assert out["body"]["finish_reason"] == "length"


def test_ordering_equivalence_stub():
    """Fixed arrival order ⇒ the HTTP path's outputs equal the offline
    drain's, request by request (the satellite contract, cheap tier)."""
    mk = lambda: StubScheduler(n_slots=2, steps_per_segment=3)
    subs = [SubmitRequest(prompt=[7 + i, 3 * i + 1], max_new_tokens=4 + i)
            for i in range(6)]
    offline = drain_offline(mk(), subs)

    async def fn(fd):
        conns = []
        for s in subs:  # await each response head: fixes arrival order
            conns.append(await open_generate(
                HOST, fd.port, _gen_payload(s.prompt, s.max_new_tokens)))
        outs = []
        for reader, writer, status, _h in conns:
            assert status == 200
            toks = []
            while True:
                ev = await read_sse_event(reader)
                if ev is None or ev.get("event") == "done":
                    outs.append((toks, ev["data"]["tokens"]))
                    break
                if ev.get("event") == "token":
                    toks.append(ev["data"]["token"])
            writer.close()
        return outs

    outs = _run(_with_fd(mk(), HttpConfig(), fn))
    for (streamed, final), want in zip(outs, offline):
        assert streamed == final == want


# ----------------------------------------------------------- backpressure


def test_backpressure_429_before_admission():
    """Past max_pending the front door answers 429 + Retry-After without
    the scheduler ever seeing the request; accepted ones finish clean."""
    sched = StubScheduler(n_slots=1, steps_per_segment=8,
                          segment_delay_s=0.15)
    cfg = HttpConfig(max_pending=2)

    async def fn(fd):
        outs = await asyncio.gather(*[
            generate(HOST, fd.port, _gen_payload([10 + i, 1], 4))
            for i in range(8)
        ])
        return outs

    outs = _run(_with_fd(sched, cfg, fn))
    rejected = [o for o in outs if o["status"] == 429]
    accepted = [o for o in outs if o["status"] == 200]
    assert rejected and accepted, [o["status"] for o in outs]
    for o in rejected:
        assert int(o["headers"]["retry-after"]) >= 1
        assert o["events"] == []  # 429s carry no SSE stream
        assert o["body"]["error"] == "overloaded"
        assert o["body"]["retry_after_s"] > 0
    for o in accepted:
        assert o["body"]["finish_reason"] == "length"
    # rejections never reached the scheduler: every minted rid was admitted
    assert sched._next_rid == len(accepted)
    assert sched.stats["retired"] == len(accepted)


def test_rate_limit_429_with_retry_after():
    policy = TenantPolicy(tenants={"a": TenantSpec(rate=0.5, burst=1)})
    sched = StubScheduler(policy=policy)

    async def fn(fd):
        first = await generate(HOST, fd.port,
                               _gen_payload([5, 5], 2, tenant="a"))
        second = await generate(HOST, fd.port,
                                _gen_payload([5, 5], 2, tenant="a"))
        return first, second

    first, second = _run(_with_fd(sched, HttpConfig(), fn))
    assert first["status"] == 200
    assert second["status"] == 429
    assert "rate limit" in second["body"]["error"]
    assert second["body"]["retry_after_s"] > 0
    assert int(second["headers"]["retry-after"]) >= 1
    assert policy.rate_rejections["a"] == 1


# ------------------------------------------------- disconnect propagation


def test_disconnect_mid_stream_reclaims_blocks_within_one_segment():
    """Closing the connection mid-stream cancels the request: the slot and
    its paged blocks return to the pool within one segment of the
    disconnect (allocator-stats assertion, as in the chaos suite)."""
    sched = StubScheduler(n_slots=2, steps_per_segment=1,
                          segment_delay_s=0.05)

    async def fn(fd):
        reader, writer, status, _h = await open_generate(
            HOST, fd.port, _gen_payload([9, 9], 60))
        assert status == 200
        for _ in range(2):  # stream is live, then vanish
            ev = await read_sse_event(reader)
            assert ev["event"] in ("token", "heartbeat")
        seg_at_disconnect = sched.stats["segments"]
        writer.close()
        deadline = asyncio.get_event_loop().time() + 10.0
        while sched.stats["cancelled"] < 1:
            assert asyncio.get_event_loop().time() < deadline, sched.stats
            await asyncio.sleep(0.01)
        return seg_at_disconnect

    seg0 = _run(_with_fd(sched, HttpConfig(heartbeat_s=0.5), fn))
    assert sched.stats["blocks_reclaimed_cancel"] > 0
    assert sched.allocator.n_free == sched.allocator.capacity
    # the cancel sweep ran within one segment of the disconnect (one
    # segment may already have been in flight when the monitor fired)
    assert sched.last_cancel_segment - seg0 <= 2, (
        sched.last_cancel_segment, seg0)


# ------------------------------------------------------- lifecycle + errors


def test_graceful_drain_completes_inflight_stream():
    """stop() mid-stream drains: the in-flight client still receives its
    full stream and terminal event, then the worker thread exits."""
    sched = StubScheduler(steps_per_segment=1, segment_delay_s=0.05)

    async def main():
        fd = FrontDoor(sched, HttpConfig())
        await fd.start()
        task = asyncio.ensure_future(
            generate(HOST, fd.port, _gen_payload([8, 8], 10)))
        while sched.stats["admitted"] < 1:  # request is mid-flight
            await asyncio.sleep(0.005)
        await fd.stop()
        out = await task
        assert out["body"]["finish_reason"] == "length"
        assert len(out["body"]["tokens"]) == 10
        assert not fd.worker.is_alive()

    _run(main())


def test_draining_returns_503():
    async def fn(fd):
        fd.draining = True
        return await generate(HOST, fd.port, _gen_payload([1, 1], 2))

    out = _run(_with_fd(StubScheduler(), HttpConfig(), fn))
    assert out["status"] == 503


def test_protocol_errors():
    async def fn(fd):
        out = {}
        out["bad_json"] = await _raw_post(fd.port, b"{not json")
        out["no_prompt"] = await generate(HOST, fd.port,
                                          {"max_new_tokens": 4})
        out["bad_type"] = await generate(
            HOST, fd.port, {"prompt": ["x"], "max_new_tokens": 4})
        out["bad_budget"] = await generate(
            HOST, fd.port, _gen_payload([1, 2], 0))
        out["get_generate"] = await http_get(HOST, fd.port, "/v1/generate")
        out["unknown"] = await http_get(HOST, fd.port, "/nope")
        out["too_big"] = await generate(
            HOST, fd.port, _gen_payload(list(range(200)), 4))
        return out

    out = _run(_with_fd(StubScheduler(),
                        HttpConfig(max_body_bytes=256), fn))
    assert out["bad_json"] == 400
    assert out["no_prompt"]["status"] == 400
    assert out["bad_type"]["status"] == 400
    assert out["bad_budget"]["status"] == 400  # scheduler-side ValueError
    assert out["get_generate"]["status"] == 405
    assert out["unknown"]["status"] == 404
    assert out["too_big"]["status"] == 413


async def _raw_post(port, body: bytes) -> int:
    reader, writer = await asyncio.open_connection(HOST, port)
    writer.write((f"POST /v1/generate HTTP/1.1\r\nHost: x\r\n"
                  f"Content-Length: {len(body)}\r\n"
                  f"Connection: close\r\n\r\n").encode() + body)
    await writer.drain()
    head = await reader.readuntil(b"\r\n\r\n")
    writer.close()
    return int(head.split(b" ", 2)[1])


def test_health_and_stats_endpoints():
    policy = TenantPolicy(tenants={"a": TenantSpec(weight=2.0)})
    sched = StubScheduler(policy=policy)

    async def fn(fd):
        await generate(HOST, fd.port, _gen_payload([5, 9], 4, tenant="a"))
        health = await http_get(HOST, fd.port, "/healthz")
        stats = await http_get(HOST, fd.port, "/v1/stats")
        return health, stats

    health, stats = _run(_with_fd(sched, HttpConfig(), fn))
    assert health["status"] == 200 and health["body"]["status"] == "ok"
    body = stats["body"]
    assert body["front_door"]["accepted"] == 1
    assert body["scheduler"]["tenant_tokens"]["a"] == 4
    assert body["tenants"]["a"]["served_tokens"] == 4
    # priced from the scheduler's counters on every call
    pricing = body["tenant_pricing"]["a"]
    assert pricing["tokens"] == 4 and pricing["share"] == 1.0
    assert pricing["j_per_token"] > 0 and pricing["tok_s"] > 0
    assert body["tenants"]["a"]["weight"] == 2.0


# ======================================================== real engine (-m http)


@pytest.fixture(scope="module")
def engines():
    """Module-scoped reduced-tinyllama engines, as in test_serve_robust."""
    import jax
    from repro.models.registry import get_arch
    from repro.serve import ServeConfig, ServeEngine
    from repro.sharding.mesh import MeshPlan

    arch = get_arch("tinyllama-1.1b", reduced=True)
    params = arch.init_params(jax.random.PRNGKey(0))

    def mk(layout, **kw):
        sc = ServeConfig(max_len=64, kv_layout=layout, block_len=8,
                         debug_invariants=True, **kw)
        return ServeEngine(arch, params, MeshPlan(), sc)

    return {"paged": mk("paged"), "oracle": mk("dense")}


def _prompt(seed, length):
    import jax
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(seed), (length,), 0, 256),
        np.int32)


def _oracle(engines, prompts, news):
    import jax.numpy as jnp
    eng = engines["oracle"]
    return [list(np.asarray(eng.generate(jnp.asarray(p)[None, :], n))[0])
            for p, n in zip(prompts, news)]


async def _collect_streams(conns):
    """Read every open SSE stream to its terminal event; returns the done
    payloads with streamed tokens cross-checked against the final list."""
    outs = []
    for reader, writer, status, _h in conns:
        assert status == 200
        toks, body = [], None
        while True:
            ev = await read_sse_event(reader)
            assert ev is not None, "stream ended without a terminal event"
            if ev.get("event") == "token":
                toks.append(ev["data"]["token"])
            elif ev.get("event") in ("done", "error"):
                body = ev["data"]
                break
        assert ev["event"] == "done", body
        assert toks == body["tokens"], "streamed tokens != terminal list"
        outs.append(body)
        writer.close()
    return outs


@pytest.mark.http
def test_http_matches_offline_scheduler(engines):
    """The ordering-equivalence satellite: for one fixed arrival order,
    greedy outputs through the HTTP path are bit-identical to the offline
    ContinuousScheduler drain (and to the sequential oracle)."""
    from repro.serve import ContinuousScheduler

    lens = [6, 9, 5, 8, 7]
    news = [12, 8, 14, 10, 9]
    prompts = [_prompt(40 + i, n) for i, n in enumerate(lens)]
    want = _oracle(engines, prompts, news)

    def mk_sched():
        return ContinuousScheduler(engines["paged"], n_slots=2,
                                   segment_len=4, n_blocks=24)

    offline_sched = mk_sched()
    handles = [offline_sched.submit(p, n) for p, n in zip(prompts, news)]
    offline_sched.run()
    offline = [list(h.tokens) for h in handles]
    assert offline == want  # scheduler vs sequential-decode oracle

    async def fn(fd):
        conns = []
        for p, n in zip(prompts, news):  # sequential heads fix arrival order
            conns.append(await open_generate(
                HOST, fd.port, _gen_payload(p, n)))
        return await _collect_streams(conns)

    outs = _run(_with_fd(mk_sched(), HttpConfig(), fn))
    for body, off in zip(outs, offline):
        assert body["finish_reason"] == "length"
        assert body["tokens"] == off  # bit-identical through the front door


@pytest.mark.http
@pytest.mark.parametrize("seed", [0, 1])
def test_chaos_under_concurrent_http_clients(engines, seed):
    """The chaos stress suite underneath concurrent HTTP clients: injected
    cancels/exhausts/slot-failures must leave survivors' outputs and
    terminal states unchanged, with every block back in the pool."""
    from repro.serve import ChaosConfig, ContinuousScheduler

    print(f"http chaos seed={seed}")  # rerun reproducibility under -s
    rng = np.random.RandomState(seed)
    n_req = 8
    lens = [int(rng.randint(3, 12)) for _ in range(n_req)]
    news = [int(rng.randint(2, 20)) for _ in range(n_req)]
    prompts = [_prompt(900 + 10 * seed + i, n) for i, n in enumerate(lens)]
    want = _oracle(engines, prompts, news)
    sched = ContinuousScheduler(
        engines["paged"], n_slots=3, segment_len=4, n_blocks=10,
        overcommit=2.0,
        chaos=ChaosConfig(seed=seed, exhaust_prob=0.15, cancel_prob=0.15,
                          slot_fail_prob=0.15))

    async def fn(fd):
        conns = []
        for p, n in zip(prompts, news):
            conns.append(await open_generate(
                HOST, fd.port, _gen_payload(p, n)))
        return await _collect_streams(conns)

    outs = _run(_with_fd(sched, HttpConfig(), fn))
    n_done = 0
    for body, w in zip(outs, want):
        if body["finish_reason"] == "length":
            n_done += 1
            assert body["tokens"] == w, (seed, body["rid"])
        else:  # chaos victim: a clean terminal event with an oracle prefix
            assert body["finish_reason"] == "cancelled", (seed, body)
            assert body["tokens"] == w[:len(body["tokens"])], (seed, body)
    assert n_done == n_req - sched.stats["cancelled"]
    assert sched.stats["cancelled"] == sched.stats["chaos_cancels"]
    assert sched.allocator.n_free == sched.allocator.capacity


# ------------------------------------------- HTTP-layer chaos clients (PR 9)


async def _malformed_client(port: int, flavor: int) -> None:
    """One misbehaving connection: garbage request line, invalid JSON, or a
    Content-Length that lies (the server's IncompleteReadError path)."""
    reader, writer = await asyncio.open_connection(HOST, port)
    try:
        if flavor == 0:
            writer.write(b"\x00\xffGARBAGE\r\n\r\n")
        elif flavor == 1:
            body = b"{not json"
            writer.write((f"POST /v1/generate HTTP/1.1\r\nHost: x\r\n"
                          f"Content-Length: {len(body)}\r\n"
                          f"Connection: close\r\n\r\n").encode() + body)
        else:
            writer.write(b"POST /v1/generate HTTP/1.1\r\nHost: x\r\n"
                         b"Content-Length: 500\r\n"
                         b"Connection: close\r\n\r\nshort")
        await writer.drain()
        if flavor != 2:  # the truncated-body client hangs up instead
            await asyncio.wait_for(reader.read(256), 5.0)
    except (ConnectionResetError, BrokenPipeError, asyncio.TimeoutError):
        pass
    finally:
        writer.close()


async def _disconnect_client(port: int, payload: dict) -> None:
    """Stream one event, then vanish mid-stream."""
    reader, writer, status, _h = await open_generate(HOST, port, payload)
    assert status == 200
    ev = await read_sse_event(reader)
    assert ev is not None
    writer.close()


async def _reading_client(port: int, payload: dict,
                          slow_s: float = 0.0) -> dict:
    """A well-behaved (possibly slow-reading) client: reads every event to
    the terminal one, stalling ``slow_s`` between reads."""
    reader, writer, status, _h = await open_generate(HOST, port, payload)
    assert status == 200
    toks = []
    try:
        while True:
            if slow_s:
                await asyncio.sleep(slow_s)  # back the socket up
            ev = await read_sse_event(reader)
            assert ev is not None, "stream ended without a terminal event"
            if ev.get("event") == "token":
                toks.append(ev["data"]["token"])
            elif ev.get("event") in ("done", "error"):
                assert ev["event"] == "done", ev
                assert toks == ev["data"]["tokens"]
                return ev["data"]
    finally:
        writer.close()


@pytest.mark.http
def test_http_chaos_clients_never_wedge_server(engines):
    """The ChaosConfig HTTP knobs (PR 9): a storm of slow readers,
    mid-stream disconnects, and malformed-frame bursts against a real
    engine.  Every well-behaved client (slow ones included) gets its full
    bit-identical stream, every disconnect is reclaimed, and the server
    answers /healthz afterwards — it never wedges."""
    import random as pyrandom

    from repro.serve import ChaosConfig, ContinuousScheduler

    chaos = ChaosConfig(seed=3, http_slow_reader_prob=0.4,
                        http_slow_reader_s=0.02,
                        http_disconnect_prob=0.3, http_malformed_prob=0.5)
    assert chaos.http_enabled and not chaos.enabled
    rng = pyrandom.Random(chaos.seed)
    n_req = 8
    np_rng = np.random.RandomState(chaos.seed)
    lens = [int(np_rng.randint(3, 12)) for _ in range(n_req)]
    news = [int(np_rng.randint(8, 20)) for _ in range(n_req)]
    prompts = [_prompt(950 + i, n) for i, n in enumerate(lens)]
    want = _oracle(engines, prompts, news)
    # seeded behavior assignment: disconnect / slow / well-behaved
    roles = []
    for _ in range(n_req):
        if rng.random() < chaos.http_disconnect_prob:
            roles.append("disconnect")
        elif rng.random() < chaos.http_slow_reader_prob:
            roles.append("slow")
        else:
            roles.append("ok")
    n_malformed = sum(rng.random() < chaos.http_malformed_prob
                      for _ in range(6))
    assert {"disconnect", "slow", "ok"} <= set(roles) and n_malformed >= 1, (
        "seed must exercise every misbehavior", roles, n_malformed)
    sched = ContinuousScheduler(engines["paged"], n_slots=3, segment_len=4,
                                n_blocks=24)

    async def fn(fd):
        tasks = []
        readers = []  # (index, task) for clients expecting a terminal event
        for i, (p, n, role) in enumerate(zip(prompts, news, roles)):
            payload = _gen_payload(p, n)
            if role == "disconnect":
                # a budget far past the disconnect point, so the cancel
                # always lands before a natural finish could race it
                tasks.append(_disconnect_client(
                    fd.port, _gen_payload(p, 40)))
            else:
                t = asyncio.ensure_future(_reading_client(
                    fd.port, payload,
                    chaos.http_slow_reader_s if role == "slow" else 0.0))
                readers.append((i, t))
                tasks.append(t)
        for k in range(n_malformed):  # the malformed burst rides alongside
            tasks.append(_malformed_client(fd.port, k % 3))
        await asyncio.wait_for(
            asyncio.gather(*tasks, return_exceptions=True), 120.0)
        bodies = {i: t.result() for i, t in readers}
        health = await http_get(HOST, fd.port, "/healthz")
        return bodies, health

    bodies, health = _run(_with_fd(sched, HttpConfig(heartbeat_s=0.5), fn))
    # the server survived the storm and still answers
    assert health["status"] == 200
    # every reader — slow ones included — got its exact greedy stream
    for i, body in bodies.items():
        assert body["finish_reason"] == "length", (i, body)
        assert body["tokens"] == want[i], i
    # disconnects were reclaimed, not leaked
    n_disc = roles.count("disconnect")
    assert sched.stats["cancelled"] == n_disc
    assert sched.allocator.n_free == sched.allocator.capacity
    assert not sched.has_work()
