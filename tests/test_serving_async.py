"""Async streaming frontend tests (stdlib asyncio — no pytest-asyncio).

The ISSUE-5 contract: N concurrent async clients with greedy sampling
receive token streams bit-identical to the same requests submitted
through the synchronous ``run_until_drained`` path (dense + one
recurrent family, no-mesh and 8-device CPU mesh), while the engine
still issues exactly ONE device call per decode step; cancellation
frees the slot / prefill lane / queue entry so the next step refills it
from the queues; bounded queues backpressure with a depth signal; TTL
expiry and submit-time rejection produce terminal Results like every
other outcome; and the HTTP layer streams SSE, cancels on disconnect,
and reports percentile metrics.

Each test drives its own event loop via ``asyncio.run`` inside a plain
sync test function, so no async test plugin is needed.
"""
import asyncio
import json
import os
import subprocess
import sys
import textwrap

import pytest

import jax

from repro import api
from repro.configs import registry
from repro.serving import (
    AsyncEngine,
    Backpressure,
    EngineClosed,
    MultiModelServer,
    Request,
    start_http_server,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _build(arch, m=2):
    cfg = registry.get_smoke_config(arch).with_(num_instances=m)
    params = api.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _server(cfg, params, **kw):
    kw.setdefault("slots_per_instance", 2)
    kw.setdefault("max_context", 48)
    kw.setdefault("temperature", 0.0)
    return MultiModelServer(cfg, params, **kw)


def _reqs():
    return [
        Request(instance=0, prompt=[1, 2, 3], max_new_tokens=4),
        Request(instance=1, prompt=[4, 5], max_new_tokens=4),
        Request(instance=0, prompt=[7], max_new_tokens=3),
        Request(instance=1, prompt=[3, 3, 3, 3, 3], max_new_tokens=3),
        Request(instance=0, prompt=[2, 2], max_new_tokens=3),
        Request(instance=1, prompt=[9, 8, 7], max_new_tokens=4),
    ]


async def _stream_all(server, reqs, **engine_kw):
    """N concurrent clients, one per request; returns {request_id:
    (streamed_tokens, Result)} plus the engine for inspection."""
    engine = AsyncEngine(server, **engine_kw)

    async def client(r):
        stream = await engine.submit(r)
        toks = [t async for t in stream]
        return stream.request_id, toks, await stream.result()

    out = await asyncio.gather(*(client(r) for r in reqs))
    await engine.aclose()
    return {rid: (toks, res) for rid, toks, res in out}


# ---------------------------------------------------------------------------
# determinism: async streams == sync run_until_drained, one call per step
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "xlstm-1.3b"])
def test_async_streams_bit_identical_to_sync(arch):
    """Concurrent async clients see exactly the tokens the synchronous
    path produces (greedy, dense + recurrent family), the streamed
    tokens equal the terminal Result's, and the driver still issues
    exactly ONE fused device call per decode step."""
    cfg, params = _build(arch)
    sync = _server(cfg, params)
    for r in _reqs():
        sync.submit(Request(r.instance, list(r.prompt), r.max_new_tokens))
    want = {r.request_id: r.tokens for r in sync.run_until_drained()}

    server = _server(cfg, params)
    calls = {"n": 0}
    inner = server._step

    def counting_step(*a, **k):
        calls["n"] += 1
        return inner(*a, **k)

    server._step = counting_step
    got = asyncio.run(_stream_all(server, _reqs()))
    assert set(got) == set(want)
    for rid, (toks, res) in got.items():
        assert res.status == "ok"
        assert toks == res.tokens
        assert toks == want[rid], (rid, toks, want[rid])
    assert server.steps > 0 and calls["n"] == server.steps


@pytest.mark.slow
def test_async_streams_identical_under_mesh():
    """Same contract on a forced 8-CPU-device (data=2, model=4) mesh:
    the async frontend sits strictly above the mesh-parametric engine,
    so sharded greedy streams match the no-mesh sync baseline for a
    dense and a recurrent family (subprocess harness as in
    test_serving_sharded.py)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import asyncio
        import jax
        from repro.launch.compat import make_host_mesh
        import numpy as np
        from repro import api
        from repro.configs import registry
        from repro.models import common as C
        from repro.serving import AsyncEngine, MultiModelServer, Request

        assert len(jax.devices()) == 8, jax.devices()
        mesh = make_host_mesh((2, 4))
        M = 2

        def build(arch):
            cfg1 = registry.get_smoke_config(arch).with_(
                num_instances=1, dtype="float32", param_dtype="float32")
            cfg = cfg1.with_(num_instances=M)
            keys = jax.random.split(jax.random.PRNGKey(0), M)
            merged = C.merge_instances(
                [api.init(cfg1, k) for k in keys], api.axes(cfg1))
            return cfg, merged

        def mk_reqs(cfg, n=5, max_new=4):
            rng = np.random.default_rng(0)
            return [Request(instance=i % M,
                            prompt=rng.integers(1, cfg.vocab_size,
                                size=int(rng.integers(2, 8))).tolist(),
                            max_new_tokens=max_new) for i in range(n)]

        async def astream(server, reqs):
            engine = AsyncEngine(server)
            async def client(r):
                s = await engine.submit(r)
                toks = [t async for t in s]
                res = await s.result()
                assert res.status == "ok" and toks == res.tokens
                return s.request_id, toks
            out = dict(await asyncio.gather(*(client(r) for r in reqs)))
            await engine.aclose()
            return out

        for arch in ("tinyllama-1.1b", "xlstm-1.3b"):
            cfg, merged = build(arch)
            sync = MultiModelServer(cfg, merged, slots_per_instance=2,
                                    max_context=64)
            for r in mk_reqs(cfg):
                sync.submit(r)
            want = {r.request_id: r.tokens for r in sync.run_until_drained()}
            assert all(want.values())
            meshed = MultiModelServer(cfg, merged, slots_per_instance=2,
                                      max_context=64, mesh=mesh)
            got = asyncio.run(astream(meshed, mk_reqs(cfg)))
            assert got == want, (arch, got, want)
            print(arch, "async mesh streams OK")
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=REPO, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert "xlstm-1.3b async mesh streams OK" in r.stdout


# ---------------------------------------------------------------------------
# cancellation at every lifecycle stage
# ---------------------------------------------------------------------------


def test_cancel_mid_decode_frees_slot_and_next_step_refills():
    """Cancelling a decoding request frees its grid slot immediately;
    the very next engine step admits the queued successor into it."""
    cfg, params = _build("tinyllama-1.1b")
    server = _server(cfg, params, slots_per_instance=1)
    victim = Request(instance=0, prompt=[1, 2, 3], max_new_tokens=64)
    waiter = Request(instance=0, prompt=[4, 5], max_new_tokens=3)
    vid = server.submit(victim)
    wid = server.submit(waiter)
    while not server.generated.get(vid):
        server.step()                      # victim is now decoding
    assert server.scheduler.depth(0) == 1  # waiter still queued
    res = server.cancel(vid)
    assert res is not None and res.status == "cancelled"
    assert res.tokens and res.request_id == vid
    assert not server.slot_busy[0, 0]      # slot freed within the cancel
    server.step()                          # next step refills from the queue
    assert server.slot_busy[0, 0]
    assert server.active[0][0].request_id == wid
    done = {r.request_id: r for r in server.run_until_drained()}
    assert done[wid].status == "ok" and len(done[wid].tokens) == 3
    # cancelled request is gone for good
    assert server.cancel(vid) is None


def test_cancel_mid_prefill_frees_lane_and_reserved_slot():
    cfg, params = _build("tinyllama-1.1b")
    server = _server(cfg, params, slots_per_instance=1, prefill_chunk=2,
                     chunk_budget=1, max_context=64)
    long = Request(instance=0, prompt=list(range(1, 33)), max_new_tokens=2)
    lid = server.submit(long)
    server.step()                          # admitted to a lane, still prefilling
    assert server.slot_prefilling[0, 0] and server.prefill.in_flight() == 1
    res = server.cancel(lid)
    assert res is not None and res.status == "cancelled" and res.tokens == []
    assert server.prefill.in_flight() == 0
    assert not server.slot_busy[0, 0] and not server.slot_prefilling[0, 0]
    # the freed lane serves the next request exactly
    after = Request(instance=0, prompt=[5, 6, 7], max_new_tokens=3)
    server.submit(after)
    done = server.run_until_drained()
    assert len(done) == 1 and done[0].status == "ok" and len(done[0].tokens) == 3


def test_cancel_mid_queue_and_async_terminal_results():
    """Async cancel of a queued request yields a terminal cancelled
    Result with no tokens; the other requests are untouched."""
    cfg, params = _build("tinyllama-1.1b")
    server = _server(cfg, params, slots_per_instance=1)

    async def run():
        engine = AsyncEngine(server)
        blocker = await engine.submit(
            Request(instance=0, prompt=[1, 2, 3], max_new_tokens=6))
        queued = await engine.submit(
            Request(instance=0, prompt=[4, 5], max_new_tokens=4))
        assert await queued.cancel()
        res_q = await queued.result()
        res_b = await blocker.result()
        assert not await queued.cancel()   # already terminal
        await engine.aclose()
        return res_q, res_b

    res_q, res_b = asyncio.run(run())
    assert res_q.status == "cancelled" and res_q.tokens == []
    assert res_b.status == "ok" and len(res_b.tokens) == 6


# ---------------------------------------------------------------------------
# backpressure / TTL / rejection
# ---------------------------------------------------------------------------


def test_backpressure_bounded_queue_rejects_and_awaits():
    cfg, params = _build("tinyllama-1.1b")
    server = _server(cfg, params, slots_per_instance=1)

    async def run():
        engine = AsyncEngine(server, max_queue_depth=1)
        # slots=1: the first request occupies the slot, the second sits
        # queued at the bound, so a third non-waiting submit must bounce
        first = await engine.submit(
            Request(instance=0, prompt=[1, 2], max_new_tokens=24))
        # wait until the first request actually holds the slot (its
        # queue entry is gone) so the queued depth below is exactly 1
        while server.scheduler.depth(0) > 0 or not server.slot_busy[0, 0]:
            await asyncio.sleep(0.005)
        second = await engine.submit(
            Request(instance=0, prompt=[3, 4], max_new_tokens=2))
        try:
            await engine.submit(
                Request(instance=0, prompt=[5], max_new_tokens=2), wait=False)
            raised = None
        except Backpressure as e:
            raised = e
        assert raised is not None
        assert raised.instance == 0
        assert raised.depth >= 1 and raised.limit == 1
        # other instances are not throttled by instance 0's queue
        other = await engine.submit(
            Request(instance=1, prompt=[6], max_new_tokens=2), wait=False)
        # wait=True: parks until the queue drains, then admits
        third = await engine.submit(
            Request(instance=0, prompt=[5], max_new_tokens=2), wait=True)
        results = [await s.result() for s in (first, second, third, other)]
        await engine.aclose()
        return results

    results = asyncio.run(run())
    assert [r.status for r in results] == ["ok"] * 4


def test_ttl_expiry_returns_expired_result():
    cfg, params = _build("tinyllama-1.1b")
    server = _server(cfg, params, slots_per_instance=1)

    async def run():
        engine = AsyncEngine(server)
        blocker = await engine.submit(
            Request(instance=0, prompt=[1, 2], max_new_tokens=12))
        doomed = await engine.submit(
            Request(instance=0, prompt=[3, 4], max_new_tokens=4), ttl_s=0.0)
        res_d = await doomed.result()
        res_b = await blocker.result()
        await engine.aclose()
        return res_d, res_b

    res_d, res_b = asyncio.run(run())
    assert res_d.status == "expired" and res_d.tokens == []
    assert res_d.error == "deadline exceeded"
    assert res_b.status == "ok" and len(res_b.tokens) == 12


def test_submit_validation_same_for_sync_raise_and_async_result():
    """The satellite contract: empty prompts and too-long prompts go
    through ONE validation path — the sync API raises, the async API
    returns an already-terminal rejected stream, with the same
    messages."""
    cfg, params = _build("tinyllama-1.1b")
    server = _server(cfg, params, max_context=32)
    bad = [
        Request(instance=0, prompt=[], max_new_tokens=4),
        Request(instance=0, prompt=list(range(1, 200)), max_new_tokens=4),
        Request(instance=7, prompt=[1], max_new_tokens=4),
        Request(instance=0, prompt=[1], max_new_tokens=0),
    ]
    sync_errors = []
    for r in bad:
        with pytest.raises(ValueError) as ei:
            server.submit(Request(r.instance, list(r.prompt), r.max_new_tokens))
        sync_errors.append(str(ei.value))

    async def run():
        engine = AsyncEngine(server)
        out = []
        for r in bad:
            stream = await engine.submit(
                Request(r.instance, list(r.prompt), r.max_new_tokens))
            assert [t async for t in stream] == []
            out.append(await stream.result())
        # a valid request on the same engine still serves
        ok = await engine.submit(Request(instance=0, prompt=[1, 2],
                                         max_new_tokens=2))
        res = await ok.result()
        await engine.aclose()
        return out, res

    rejected, ok = asyncio.run(run())
    assert [r.status for r in rejected] == ["rejected"] * 4
    assert [r.error for r in rejected] == sync_errors
    assert ok.status == "ok" and len(ok.tokens) == 2
    snap = server.metrics.snapshot()
    assert snap["rejected"] == 6   # 3 sync + 3 async on instance 0
    assert snap["instances"][0]["rejected"] == 6


def test_finish_reason_distinguishes_eos_from_length():
    """An EOS-terminated decode reports finish_reason "stop"; a
    max_new_tokens-terminated one reports "length" (what the HTTP layer
    surfaces to OpenAI-style clients)."""
    cfg, params = _build("tinyllama-1.1b")
    ref = _server(cfg, params)
    rid = ref.submit(Request(instance=0, prompt=[1, 2, 3], max_new_tokens=4))
    toks = {r.request_id: r for r in ref.run_until_drained()}[rid].tokens
    assert len(toks) == 4

    server = _server(cfg, params, eos_id=toks[1])
    a = server.submit(Request(instance=0, prompt=[1, 2, 3], max_new_tokens=4))
    b = server.submit(Request(instance=1, prompt=[4, 5], max_new_tokens=4))
    res = {r.request_id: r for r in server.run_until_drained()}
    assert res[a].tokens == toks[:2]          # stopped AT the eos token
    assert res[a].finish_reason == "stop"
    assert toks[1] not in res[b].tokens       # (other stream avoids eos)
    assert res[b].finish_reason == "length"


def test_submit_after_close_raises():
    cfg, params = _build("tinyllama-1.1b")
    server = _server(cfg, params)

    async def run():
        engine = AsyncEngine(server)
        s = await engine.submit(Request(instance=0, prompt=[1], max_new_tokens=2))
        await s.result()
        await engine.drain()
        with pytest.raises(EngineClosed):
            await engine.submit(Request(instance=0, prompt=[2], max_new_tokens=2))

    asyncio.run(run())


def test_aclose_without_drain_cancels_live_requests():
    cfg, params = _build("tinyllama-1.1b")
    server = _server(cfg, params, slots_per_instance=1)

    async def run():
        engine = AsyncEngine(server)
        a = await engine.submit(Request(instance=0, prompt=[1, 2],
                                        max_new_tokens=40))
        b = await engine.submit(Request(instance=0, prompt=[3],
                                        max_new_tokens=4))
        # let the first request start decoding before tearing down
        async for _ in a:
            break
        await engine.aclose(drain=False)
        return await a.result(), await b.result()

    res_a, res_b = asyncio.run(run())
    assert res_a.status == "cancelled" and len(res_a.tokens) >= 1
    assert res_b.status == "cancelled"
    assert not server.busy()


# ---------------------------------------------------------------------------
# scheduler fairness under churn (satellite)
# ---------------------------------------------------------------------------


def test_token_budget_never_starves_under_cancellation_churn():
    """Property-style: under token-budget admission with requests being
    cancelled mid-queue and mid-decode at every step, every instance
    still completes all of its surviving requests, every cancel frees
    its slot within the step, and freed slots are refilled from the
    queues on the next step."""
    cfg, params = _build("tinyllama-1.1b", m=3)
    for seed in range(3):
        server = _server(cfg, params, slots_per_instance=1,
                         scheduler="token-budget", max_context=64)
        import numpy as np
        rng = np.random.default_rng(seed)
        reqs = [
            Request(instance=i % 3,
                    prompt=rng.integers(1, cfg.vocab_size,
                                        size=int(rng.integers(1, 7))).tolist(),
                    max_new_tokens=int(rng.integers(2, 6)))
            for i in range(15)
        ]
        ids = [server.submit(r) for r in reqs]
        by_id = dict(zip(ids, reqs))
        cancelled, done = set(), {}
        steps = 0
        while server.busy() and steps < 500:
            # churn: cancel a random queued request and, sometimes, a
            # random decoding one
            queued = [
                r.request_id
                for q in server.scheduler.queues for r in q
            ]
            if queued and rng.random() < 0.5:
                rid = int(rng.choice(queued))
                res = server.cancel(rid)
                assert res is not None and res.status == "cancelled"
                cancelled.add(rid)
            decoding = [
                r.request_id
                for row in server.active for r in row
                if r is not None and server.generated.get(r.request_id)
            ]
            if decoding and rng.random() < 0.25:
                rid = int(rng.choice(decoding))
                m = by_id[rid].instance
                b = next(bb for bb in range(server.b)
                         if server.active[m][bb] is not None
                         and server.active[m][bb].request_id == rid)
                res = server.cancel(rid)
                assert res is not None and res.status == "cancelled"
                assert not server.slot_busy[m, b]   # freed within the step
                cancelled.add(rid)
            for r in server.step():
                done[r.request_id] = r
            steps += 1
        assert not server.busy(), "churned workload did not drain"
        # every surviving request completed with its full token budget —
        # no instance was starved by churn on the others
        survivors = [rid for rid in ids if rid not in cancelled]
        assert set(done) == set(survivors)
        for rid in survivors:
            assert done[rid].status == "ok"
            assert len(done[rid].tokens) == by_id[rid].max_new_tokens
        per_inst = {i: sum(1 for rid in survivors if by_id[rid].instance == i)
                    for i in range(3)}
        for i, n in per_inst.items():
            got = sum(1 for rid in done if by_id[rid].instance == i)
            assert got == n, (seed, i, got, n)


# ---------------------------------------------------------------------------
# HTTP layer
# ---------------------------------------------------------------------------


async def _http_post(port, path, payload):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    body = json.dumps(payload).encode()
    writer.write(
        f"POST {path} HTTP/1.1\r\nHost: t\r\nContent-Type: application/json"
        f"\r\nContent-Length: {len(body)}\r\n\r\n".encode() + body
    )
    await writer.drain()
    raw = await reader.read()
    writer.close()
    await writer.wait_closed()
    head, _, rest = raw.partition(b"\r\n\r\n")
    return head.decode("latin-1"), rest


async def _http_get(port, path):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(f"GET {path} HTTP/1.1\r\nHost: t\r\n\r\n".encode())
    await writer.drain()
    raw = await reader.read()
    writer.close()
    await writer.wait_closed()
    head, _, rest = raw.partition(b"\r\n\r\n")
    return head.decode("latin-1"), rest


def _sse_events(rest: bytes):
    out = []
    for line in rest.split(b"\n\n"):
        if line.startswith(b"data: ") and line != b"data: [DONE]":
            out.append(json.loads(line[len(b"data: "):]))
    return out


def test_http_completions_sse_matches_sync_and_metrics():
    """POST /v1/completions with stream=true delivers exactly the sync
    engine's greedy tokens as SSE chunks (finish_reason on the last),
    the non-stream flavor returns them in one JSON body, and
    GET /metrics carries the TTFT/ITL percentile blocks."""
    cfg, params = _build("tinyllama-1.1b")
    sync = _server(cfg, params)
    sid = sync.submit(Request(instance=0, prompt=[1, 2, 3], max_new_tokens=4))
    want = {r.request_id: r.tokens for r in sync.run_until_drained()}[sid]

    server = _server(cfg, params)

    async def run():
        engine = AsyncEngine(server)
        http = await start_http_server(engine, port=0)
        port = http.sockets[0].getsockname()[1]

        head, rest = await _http_post(port, "/v1/completions", {
            "model": "model-0", "prompt": [1, 2, 3], "max_tokens": 4,
            "stream": True,
        })
        assert head.startswith("HTTP/1.1 200")
        assert "text/event-stream" in head
        events = _sse_events(rest)
        toks = [e["choices"][0]["token"] for e in events
                if e["choices"][0]["token"] is not None]
        assert rest.rstrip().endswith(b"data: [DONE]")
        assert events[-1]["choices"][0]["finish_reason"] == "length"

        head2, body2 = await _http_post(port, "/v1/completions", {
            "model": 0, "prompt": [1, 2, 3], "max_tokens": 4,
        })
        assert head2.startswith("HTTP/1.1 200")
        payload = json.loads(body2)

        # invalid requests map to HTTP codes, not raises
        head3, _ = await _http_post(port, "/v1/completions",
                                    {"model": "nope", "prompt": [1]})
        head4, _ = await _http_post(port, "/v1/completions",
                                    {"model": 0, "prompt": []})
        head5, _ = await _http_post(port, "/v1/completions",
                                    {"model": 0, "prompt": "text"})

        mh, mb = await _http_get(port, "/metrics")
        lh, lb = await _http_get(port, "/v1/models")

        http.close()
        await http.wait_closed()
        await engine.aclose()
        return toks, payload, (head3, head4, head5), (mh, json.loads(mb)), \
            json.loads(lb)

    toks, payload, errheads, (mh, snap), models = asyncio.run(run())
    assert toks == want
    assert payload["choices"][0]["tokens"] == want
    assert payload["choices"][0]["finish_reason"] == "length"
    assert errheads[0].startswith("HTTP/1.1 404")
    assert errheads[1].startswith("HTTP/1.1 400")
    assert errheads[2].startswith("HTTP/1.1 400")
    assert mh.startswith("HTTP/1.1 200")
    assert snap["generated_tokens"] == 8
    assert snap["ttft_ms"] is not None
    assert set(snap["ttft_ms"]) == {"p50", "p95", "p99"}
    assert snap["itl_ms"] is not None
    assert snap["instances"][0]["ttft_ms"] is not None
    assert [m["id"] for m in models["data"]] == ["model-0", "model-1"]


def test_http_client_disconnect_cancels_request():
    """Dropping the SSE connection mid-stream cancels the request: the
    engine frees its slot and the workload drains without it."""
    cfg, params = _build("tinyllama-1.1b")
    server = _server(cfg, params, slots_per_instance=1)

    async def run():
        engine = AsyncEngine(server)
        http = await start_http_server(engine, port=0)
        port = http.sockets[0].getsockname()[1]

        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        body = json.dumps({"model": 0, "prompt": [1, 2], "max_tokens": 500,
                           "stream": True}).encode()
        writer.write(
            f"POST /v1/completions HTTP/1.1\r\nHost: t\r\n"
            f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
        await writer.drain()
        # read until the first token chunk arrives, then vanish
        buf = b""
        while b"\n\n" not in buf.partition(b"\r\n\r\n")[2]:
            chunk = await reader.read(4096)
            assert chunk, "server closed before first token"
            buf += chunk
        writer.close()
        await writer.wait_closed()
        # the engine notices the disconnect and cancels within a few
        # steps; a successor request then gets the slot
        for _ in range(200):
            if not server.busy():
                break
            await asyncio.sleep(0.02)
        assert not server.busy(), "disconnect did not cancel the request"
        after = await engine.submit(Request(instance=0, prompt=[6],
                                            max_new_tokens=2))
        res = await after.result()
        http.close()
        await http.wait_closed()
        await engine.aclose()
        return res

    res = asyncio.run(run())
    assert res.status == "ok" and len(res.tokens) == 2
    assert server.metrics.snapshot()["cancelled"] == 1


def test_http_nonstream_disconnect_cancels_request():
    """The non-streaming flavor must not hold a decode slot for a
    client that hung up: disconnect while the completion is in flight
    cancels it."""
    cfg, params = _build("tinyllama-1.1b")
    server = _server(cfg, params, slots_per_instance=1)

    async def run():
        engine = AsyncEngine(server)
        http = await start_http_server(engine, port=0)
        port = http.sockets[0].getsockname()[1]

        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        body = json.dumps({"model": 0, "prompt": [1, 2],
                           "max_tokens": 500}).encode()
        writer.write(
            f"POST /v1/completions HTTP/1.1\r\nHost: t\r\n"
            f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
        await writer.drain()
        # give the request time to admit and start decoding, then vanish
        # without ever reading the (pending) response
        for _ in range(200):
            if server.metrics.snapshot()["generated_tokens"] > 0:
                break
            await asyncio.sleep(0.02)
        writer.close()
        await writer.wait_closed()
        for _ in range(200):
            if not server.busy():
                break
            await asyncio.sleep(0.02)
        assert not server.busy(), "disconnect did not cancel the request"
        http.close()
        await http.wait_closed()
        await engine.aclose()

    asyncio.run(run())
    snap = server.metrics.snapshot()
    assert snap["cancelled"] == 1
    assert snap["generated_tokens"] < 500
