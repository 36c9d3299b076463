"""Deterministic chaos suite for the fault-tolerant serving core
(DESIGN.md §6.8).

The ISSUE-9 contract:

* the fault injector is seedable and deterministic — same plan + seed
  ⇒ same fault schedule ⇒ same recovered streams — and ZERO injector
  code runs when disarmed (bombed-methods proof, same discipline as the
  PR-6 tracer guard);
* a greedy stream interrupted by a mid-decode driver crash and
  recovered by the Supervisor is **bit-identical** to the uninterrupted
  run — no token lost, none duplicated — sync engine, async frontend,
  and 8-device mesh (subprocess);
* an injected NaN on instance i quarantines ONLY row i (its requests
  503 at submit) while the other M−1 instances' streams stay
  byte-identical to the fault-free run; probation un-quarantines;
* the watchdog fires on an injected stall and recovery still yields
  bit-identical streams;
* driver death without a Supervisor propagates: streams end with
  terminal ``status="error"`` Results (keeping delivered tokens),
  pending submits get ``EngineClosed``, ``drain()``/``aclose()``
  return instead of hanging (satellite 1);
* an exception mid-``step()`` never leaks a busy slot or prefill lane
  (satellite 2);
* overload brownout sheds by queue age and caps ``max_new`` in
  degraded mode.

Every test pins its fault schedule with ``at_call``/``every`` triggers
or a fixed ``seed``, so the suite is reproducible run-to-run.
"""
import asyncio
import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

import jax

from repro import api
from repro.configs import registry
from repro.serving import (
    AsyncEngine,
    BrownoutPolicy,
    EngineClosed,
    FaultInjected,
    FaultInjector,
    FaultSpec,
    HealthMonitor,
    MultiModelServer,
    Request,
    Result,
    Supervisor,
    start_http_server,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARCH = "tinyllama-1.1b"


def _build(m=2):
    cfg = registry.get_smoke_config(ARCH).with_(num_instances=m)
    params = api.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _server(cfg, params, **kw):
    kw.setdefault("slots_per_instance", 2)
    kw.setdefault("max_context", 48)
    kw.setdefault("temperature", 0.0)
    return MultiModelServer(cfg, params, **kw)


def _reqs(m=2):
    base = [
        Request(instance=0, prompt=[1, 2, 3], max_new_tokens=4),
        Request(instance=1, prompt=[4, 5], max_new_tokens=4),
        Request(instance=0, prompt=[7], max_new_tokens=3),
        Request(instance=1, prompt=[3, 3, 3, 3, 3], max_new_tokens=3),
    ]
    if m > 2:
        base.append(Request(instance=2, prompt=[9, 8], max_new_tokens=4))
    return base


def _clean_streams(cfg, params, m=2, **kw):
    """The fault-free greedy reference: {request_id: (tokens, status)}."""
    srv = _server(cfg, params, **kw)
    for r in _reqs(m):
        srv.try_submit(r)
    return {r.request_id: (r.tokens, r.status)
            for r in srv.run_until_drained()}


async def _stream_all(engine, reqs):
    async def client(r):
        stream = await engine.submit(r)
        toks = [t async for t in stream]
        return stream.request_id, toks, await stream.result()

    return await asyncio.gather(*(client(r) for r in reqs))


# ---------------------------------------------------------------------------
# fault injector: zero-cost when disarmed, deterministic when armed
# ---------------------------------------------------------------------------


def test_disarmed_injector_runs_no_code(monkeypatch):
    """Every fault site is guarded by ``if faults.armed:`` — with the
    injector disarmed, a workload must complete even when every
    injector method is replaced with a bomb (the PR-6 tracer
    discipline: disabled means no code, not cheap code)."""
    cfg, params = _build()
    inj = FaultInjector([FaultSpec(site="decode", at_call=1)])

    def boom(*a, **k):
        raise AssertionError("injector code ran while disarmed")

    monkeypatch.setattr(inj, "on_call", boom)
    monkeypatch.setattr(inj, "arm", boom)
    monkeypatch.setattr(inj, "reset", boom)
    server = _server(cfg, params, faults=inj)
    for r in _reqs():
        server.try_submit(r)
    out = server.run_until_drained()
    assert all(r.status == "ok" for r in out)
    assert inj.calls == {} and inj.fired == []


def test_fault_schedule_is_deterministic():
    """Probabilistic plans replay identically for a fixed seed: the
    ``fired`` fingerprint (site, call index, kind) matches across runs,
    and a different seed produces a different schedule."""

    def schedule(seed):
        inj = FaultInjector(
            [FaultSpec(site="decode", kind="nan", prob=0.3, times=None)],
            seed=seed).arm()
        for _ in range(64):
            inj.on_call("decode")
        return list(inj.fired)

    a, b = schedule(7), schedule(7)
    assert a == b and a            # identical, and the plan does fire
    assert schedule(8) != a
    # reset() rewinds counters AND the rng: the schedule replays
    inj = FaultInjector(
        [FaultSpec(site="decode", kind="nan", prob=0.3, times=None)],
        seed=7).arm()
    for _ in range(64):
        inj.on_call("decode")
    first = list(inj.fired)
    inj.reset()
    for _ in range(64):
        inj.on_call("decode")
    assert inj.fired == first == a


def test_fault_plan_json_roundtrip(tmp_path):
    plan = {"seed": 3, "faults": [
        {"site": "driver", "at_call": 2},
        {"site": "decode", "kind": "nan", "instance": 1, "every": 5,
         "times": 2},
    ]}
    inline = FaultInjector.from_json(json.dumps(plan))
    p = tmp_path / "plan.json"
    p.write_text(json.dumps(plan))
    from_file = FaultInjector.from_json(str(p))
    for inj in (inline, from_file):
        assert inj.seed == 3 and len(inj.plan) == 2
        assert inj.plan[1].kind == "nan" and inj.plan[1].instance == 1
    with pytest.raises(ValueError):
        FaultSpec(site="nowhere", at_call=1)
    with pytest.raises(ValueError):
        FaultSpec(site="decode")           # no trigger


def test_checkpoint_fault_site(tmp_path):
    from repro.checkpoint import store

    tree = {"w": jax.numpy.ones((2, 2))}
    store.save(tmp_path / "ckpt", tree)
    inj = FaultInjector([FaultSpec(site="checkpoint", at_call=1)]).arm()
    with pytest.raises(FaultInjected):
        store.restore(tmp_path / "ckpt", tree, faults=inj)
    # the spec fired once (times=1): the retry succeeds
    back = store.restore(tmp_path / "ckpt", tree, faults=inj)
    assert back["w"].shape == (2, 2)


# ---------------------------------------------------------------------------
# crash recovery: bit-identical greedy streams
# ---------------------------------------------------------------------------


def test_sync_crash_recovery_bit_identical():
    """Mid-decode engine crash, recovered by reset + requeue with the
    delivered prefix: terminal streams AND the on_token hook stream are
    bit-identical to the uninterrupted run."""
    cfg, params = _build()
    want = _clean_streams(cfg, params)

    inj = FaultInjector([FaultSpec(site="decode", at_call=3)])
    srv = _server(cfg, params, faults=inj)
    emitted = {}
    srv.on_token = lambda rid, tok, fin: emitted.setdefault(rid, []).append(tok)
    for r in _reqs():
        srv.try_submit(r)
    inj.arm()
    done, crashes = [], 0
    while srv.busy() or srv._pending_failures:
        try:
            done.extend(srv.step())
        except FaultInjected:
            crashes += 1
            live = srv.reset_serving_state()
            for req, _gen in live:
                srv.requeue(req, emitted=list(emitted.get(req.request_id, [])))
    assert crashes == 1
    got = {r.request_id: (r.tokens, r.status) for r in done}
    assert got == want
    # the client-visible hook stream carries each token exactly once
    assert emitted == {rid: toks for rid, (toks, _s) in want.items()}
    assert srv.metrics.replay_mismatches == 0
    assert srv.metrics.replayed_tokens > 0


def test_supervised_async_crash_bit_identical():
    """Driver-site crash under a Supervisor: one restart, streams (both
    iterated tokens and terminal Results) bit-identical to the clean
    run, zero token duplication across the requeue."""
    cfg, params = _build()
    want = _clean_streams(cfg, params)

    inj = FaultInjector([FaultSpec(site="driver", at_call=2)])
    srv = _server(cfg, params, faults=inj)
    inj.arm()

    async def main():
        engine = AsyncEngine(srv)
        sup = Supervisor(engine, backoff_base_s=0.001)
        async with sup:
            out = await _stream_all(engine, _reqs())
        return out, sup

    out, sup = asyncio.run(main())
    got = {rid: (toks, res.status) for rid, toks, res in out}
    assert got == want
    assert all(list(res.tokens) == toks for _rid, toks, res in out)
    assert sup.restarts == 1
    snap = sup.snapshot()
    assert snap["driver_restarts"] == 1
    assert snap["request_retries"] == len(_reqs())
    assert snap["last_recovery_s"] is not None
    # the engine's metrics carry the supervision block
    assert srv.metrics.snapshot()["resilience"]["driver_restarts"] == 1
    assert srv.metrics.replay_mismatches == 0


def test_watchdog_fires_on_injected_stall():
    """A decode step stalled past the watchdog deadline is detected,
    the stalled step is waited out (soft path: executor threads cannot
    be killed), and recovery still yields bit-identical streams."""
    cfg, params = _build()

    def warm(s):
        s.try_submit(Request(instance=0, prompt=[1, 2], max_new_tokens=2))
        s.run_until_drained()

    srv0 = _server(cfg, params)
    warm(srv0)                 # align request-id ranges with the faulted run
    for r in _reqs():
        srv0.try_submit(r)
    want = {r.request_id: (r.tokens, r.status)
            for r in srv0.run_until_drained()}

    inj = FaultInjector([FaultSpec(site="decode", kind="stall",
                                   stall_s=1.0, at_call=2)])
    srv = _server(cfg, params, faults=inj)
    warm(srv)                  # compiles must not trip the watchdog
    inj.arm()

    async def main():
        engine = AsyncEngine(srv)
        sup = Supervisor(engine, watchdog_s=0.25, backoff_base_s=0.001)
        async with sup:
            out = await _stream_all(engine, _reqs())
        return out, sup

    out, sup = asyncio.run(main())
    got = {rid: (toks, res.status) for rid, toks, res in out}
    assert got == want
    assert sup.watchdog_timeouts == 1 and sup.restarts == 1


def test_retry_budget_exhaustion_gives_up_cleanly():
    """A driver that crashes on EVERY step exhausts max_restarts: every
    stream ends with a terminal error Result (no hang), the engine
    refuses new work, and the counters record the give-up."""
    cfg, params = _build()
    inj = FaultInjector([FaultSpec(site="driver", every=1, times=None)])
    srv = _server(cfg, params, faults=inj)
    inj.arm()

    async def main():
        engine = AsyncEngine(srv)
        sup = Supervisor(engine, max_restarts=2, backoff_base_s=0.001,
                         max_retries=100)   # restart budget trips first
        sup.start()
        out = await asyncio.wait_for(_stream_all(engine, _reqs()), 60)
        with pytest.raises(EngineClosed):
            await engine.submit(Request(instance=0, prompt=[1],
                                        max_new_tokens=1))
        await asyncio.wait_for(engine.aclose(), 10)
        return out, sup

    out, sup = asyncio.run(main())
    assert all(res.status == "error" for _rid, _t, res in out)
    assert all("permanently" in res.error for _rid, _t, res in out)
    assert sup.restarts == 2


# ---------------------------------------------------------------------------
# satellite 1: unsupervised driver death propagates, nothing hangs
# ---------------------------------------------------------------------------


def test_unsupervised_driver_death_propagates():
    cfg, params = _build()
    inj = FaultInjector([FaultSpec(site="decode", at_call=2)])
    srv = _server(cfg, params, faults=inj)
    inj.arm()

    async def main():
        engine = AsyncEngine(srv)
        s1 = await engine.submit(Request(instance=0, prompt=[1, 2, 3],
                                         max_new_tokens=6))
        s2 = await engine.submit(Request(instance=1, prompt=[4, 5],
                                         max_new_tokens=6))
        r1 = await asyncio.wait_for(s1.result(), 120)
        r2 = await asyncio.wait_for(s2.result(), 120)
        # terminal error Results carrying the already-delivered tokens
        # (decode call 1 landed before the crash)
        assert r1.status == "error" and "driver failed" in r1.error
        assert r2.status == "error"
        assert r1.tokens == list(s1.emitted) and len(r1.tokens) >= 1
        assert engine.driver_status() == "failed"
        with pytest.raises(EngineClosed):
            await engine.submit(Request(instance=0, prompt=[1],
                                        max_new_tokens=1))
        # neither drain nor aclose hangs or re-raises
        await asyncio.wait_for(engine.drain(), 10)
        await asyncio.wait_for(engine.aclose(), 10)

    asyncio.run(main())


# ---------------------------------------------------------------------------
# satellite 2: exception mid-step never leaks a slot
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("site", ["scatter", "prefill"])
def test_step_exception_leaks_no_slot(site):
    cfg, params = _build()
    inj = FaultInjector([FaultSpec(site=site, at_call=1)])
    srv = _server(cfg, params, faults=inj)
    for r in _reqs():
        srv.try_submit(r)
    inj.arm()
    out = srv.run_until_drained()
    # the hit request(s) failed terminally; nothing hangs, nothing leaks
    assert any(r.status == "error" for r in out)
    assert not srv.slot_busy.any() and not srv.slot_prefilling.any()
    assert srv.prefill.in_flight() == 0 and not srv._reserved
    assert srv.scheduler.total_pending() == 0
    # ...and the engine still serves: the failed slot is reusable
    srv.try_submit(Request(instance=0, prompt=[7], max_new_tokens=3))
    again = srv.run_until_drained()
    assert [r.status for r in again] == ["ok"]


# ---------------------------------------------------------------------------
# NaN guard -> quarantine: one instance 503s, the rest are untouched
# ---------------------------------------------------------------------------


def test_nan_quarantines_only_poisoned_instance():
    cfg, params = _build(m=3)
    want = _clean_streams(cfg, params, m=3)

    inj = FaultInjector([FaultSpec(site="decode", kind="nan", instance=1,
                                   at_call=2)])
    hm = HealthMonitor(3, quarantine_steps=4)
    srv = _server(cfg, params, faults=inj, health=hm)
    for r in _reqs(3):
        srv.try_submit(r)
    inj.arm()
    got = {r.request_id: (r.tokens, r.status) for r in srv.run_until_drained()}

    # instance 1's request died on the token guard; every other stream
    # is byte-identical to the fault-free run
    assert got[1][1] == "error" and got[3][1] == "error"
    for rid in want:
        if rid not in (1, 3):
            assert got[rid] == want[rid], (rid, got[rid], want[rid])
    assert hm.states() == ["healthy", "quarantined", "healthy"]

    # submit to row 1 -> born-terminal "unavailable"; rows 0/2 unaffected
    rej = srv.try_submit(Request(instance=1, prompt=[1], max_new_tokens=2))
    assert isinstance(rej, Result) and rej.status == "unavailable"
    srv.try_submit(Request(instance=0, prompt=[1, 2, 3], max_new_tokens=4))
    ok = srv.run_until_drained()
    assert ok[0].status == "ok" and ok[0].tokens == want[0][0]

    # quarantine ages into probation; a served success restores healthy
    rounds = 0
    while hm.state(1) == "quarantined" and rounds < 50:
        srv.try_submit(Request(instance=0, prompt=[9], max_new_tokens=1))
        srv.run_until_drained()
        rounds += 1
    assert hm.state(1) == "probation"
    srv.try_submit(Request(instance=1, prompt=[4, 5], max_new_tokens=4))
    back = srv.run_until_drained()
    assert back[-1].status == "ok" and back[-1].tokens == want[1][0]
    assert hm.state(1) == "healthy"
    snap = hm.snapshot()
    assert snap["quarantine_events"] == 1 and snap["poisoned_tokens"] >= 1


# ---------------------------------------------------------------------------
# overload brownout: shed by age, degrade caps max_new
# ---------------------------------------------------------------------------


def test_brownout_sheds_by_queue_age():
    cfg, params = _build()
    pol = BrownoutPolicy(shed_age_s=0.05)
    srv = _server(cfg, params, policy=pol, slots_per_instance=1)
    # more work than slots: the tail queues
    old = [Request(instance=0, prompt=[1, 2], max_new_tokens=2)
           for _ in range(4)]
    for r in old:
        srv.try_submit(r)
    time.sleep(0.1)            # everything queued is now over-age
    out = srv.step()           # policy pass sheds before admission
    shed = [r for r in out if r.status == "shed"]
    assert shed and all("overload" in r.error for r in shed)
    assert pol.shed_total == len(shed)
    out = srv.run_until_drained()
    # whatever was admitted before aging still completes
    assert all(r.status == "ok" for r in out)


def test_brownout_degraded_mode_caps_max_new():
    cfg, params = _build()
    pol = BrownoutPolicy(degrade_depth=2, degrade_steps=2,
                         degraded_max_new=2)
    srv = _server(cfg, params, policy=pol, slots_per_instance=1)
    # sustained backpressure: pending >= degrade_depth for degrade_steps
    for _ in range(6):
        srv.try_submit(Request(instance=0, prompt=[1, 2],
                               max_new_tokens=8))
        srv.try_submit(Request(instance=1, prompt=[3, 4],
                               max_new_tokens=8))
    steps = 0
    while not pol.degraded and steps < 50:
        srv.step()
        steps += 1
    assert pol.degraded
    # a submission under degraded mode is capped at admission
    late = Request(instance=0, prompt=[5], max_new_tokens=16)
    srv.try_submit(late)
    assert late.max_new_tokens == 2 and pol.capped_total >= 1
    out = srv.run_until_drained()
    capped = [r for r in out if r.request_id == late.request_id]
    assert capped and capped[0].status == "ok"
    assert len(capped[0].tokens) == 2


# ---------------------------------------------------------------------------
# HTTP surface: 503 + Retry-After, /healthz, Prometheus rows
# ---------------------------------------------------------------------------


async def _raw_http(port, method, path, payload=None):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    body = b"" if payload is None else json.dumps(payload).encode()
    writer.write(
        f"{method} {path} HTTP/1.1\r\nHost: t\r\n"
        f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
    await writer.drain()
    raw = await reader.read()
    writer.close()
    await writer.wait_closed()
    head, _, rest = raw.partition(b"\r\n\r\n")
    lines = head.decode("latin-1").split("\r\n")
    status = int(lines[0].split()[1])
    headers = {}
    for ln in lines[1:]:
        k, _, v = ln.partition(":")
        headers[k.strip().lower()] = v.strip()
    return status, headers, rest


def test_http_quarantine_503_healthz_and_prometheus():
    cfg, params = _build()
    inj = FaultInjector([FaultSpec(site="decode", kind="nan", instance=0,
                                   at_call=1)])
    srv = _server(cfg, params, faults=inj,
                  health=HealthMonitor(2, quarantine_steps=1024))

    async def run():
        engine = AsyncEngine(srv)
        sup = Supervisor(engine, backoff_base_s=0.001)
        sup.start()
        http = await start_http_server(engine, port=0)
        port = http.sockets[0].getsockname()[1]
        inj.arm()
        # poison instance 0's first decode call -> its request errors
        # and row 0 quarantines; instance 1 serves normally throughout
        st, _h, body = await _raw_http(
            port, "POST", "/v1/completions",
            {"model": 0, "prompt": [1, 2, 3], "max_tokens": 4})
        assert st == 200
        assert json.loads(body)["status"] == "error"

        st, headers, body = await _raw_http(
            port, "POST", "/v1/completions",
            {"model": 0, "prompt": [1], "max_tokens": 2})
        assert st == 503
        assert "retry-after" in headers
        err = json.loads(body)["error"]
        assert err["reason"] == "unavailable"

        st, _h, body = await _raw_http(
            port, "POST", "/v1/completions",
            {"model": 1, "prompt": [4, 5], "max_tokens": 3})
        assert st == 200 and json.loads(body)["status"] == "ok"

        st, _h, body = await _raw_http(port, "GET", "/healthz")
        h = json.loads(body)
        assert st == 200
        assert h["instance_health"] == ["quarantined", "healthy"]
        assert h["resilience"]["driver_restarts"] == 0

        # Prometheus exposition carries the §6.8 rows
        snap = srv.metrics.snapshot()
        from repro.serving.obs import render_prometheus
        text = render_prometheus(snap)
        assert "repro_driver_restarts_total 0" in text
        assert "repro_request_retries_total 0" in text
        assert "repro_watchdog_timeouts_total 0" in text
        assert "repro_instances_quarantined 1" in text
        assert ('repro_instance_health_state{instance="0",'
                'state="quarantined"} 1') in text
        assert ('repro_instance_health_state{instance="1",'
                'state="healthy"} 1') in text

        http.close()
        await http.wait_closed()
        await engine.aclose()

    asyncio.run(run())


# ---------------------------------------------------------------------------
# recovery trace + requeue metrics surface
# ---------------------------------------------------------------------------


def test_recovery_events_land_in_trace_and_metrics():
    cfg, params = _build()
    inj = FaultInjector([FaultSpec(site="driver", at_call=2)])
    srv = _server(cfg, params, faults=inj)
    srv.tracer.start()
    inj.arm()

    async def main():
        engine = AsyncEngine(srv)
        sup = Supervisor(engine, backoff_base_s=0.001)
        async with sup:
            out = await _stream_all(engine, _reqs())
        return out

    out = asyncio.run(main())
    assert all(res.status == "ok" for _rid, _t, res in out)
    srv.tracer.stop()
    chrome = srv.tracer.export_chrome()
    names = {e["name"] for e in chrome["traceEvents"]}
    assert any(n.startswith("restart") for n in names)
    assert "requeue" in names
    snap = srv.metrics.snapshot()
    assert snap["requeued"] == len(_reqs())
    assert snap["replayed_tokens"] == snap["resilience"]["tokens_replayed"]
    assert snap["replay_mismatches"] == 0


# ---------------------------------------------------------------------------
# 8-device mesh: crash recovery stays bit-identical when sharded
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_supervised_crash_bit_identical_mesh():
    """The recovery invariant on a forced 8-CPU-device (2, 4) mesh:
    reset_serving_state rebuilds the sharded cache/key in place and the
    requeued greedy streams match the no-fault mesh run byte-for-byte
    (subprocess harness as in test_serving_async.py)."""
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
        from repro.serving import (AsyncEngine, FaultInjector, FaultSpec,
                                   MultiModelServer, Request, Supervisor)

        assert len(jax.devices()) == 8, jax.devices()
        mesh = make_host_mesh((2, 4))
        M = 2
        cfg1 = registry.get_smoke_config("tinyllama-1.1b").with_(
            num_instances=1, dtype="float32", param_dtype="float32")
        cfg = cfg1.with_(num_instances=M)
        keys = jax.random.split(jax.random.PRNGKey(0), M)
        merged = C.merge_instances(
            [api.init(cfg1, k) for k in keys], api.axes(cfg1))

        def mk_reqs(n=5, max_new=4):
            rng = np.random.default_rng(0)
            return [Request(instance=i % M,
                            prompt=rng.integers(1, cfg.vocab_size,
                                size=int(rng.integers(2, 8))).tolist(),
                            max_new_tokens=max_new) for i in range(n)]

        clean = MultiModelServer(cfg, merged, slots_per_instance=2,
                                 max_context=64, mesh=mesh)
        for r in mk_reqs():
            clean.submit(r)
        want = {r.request_id: (r.tokens, r.status)
                for r in clean.run_until_drained()}
        assert all(t for t, _s in want.values())

        inj = FaultInjector([FaultSpec(site="driver", at_call=2)])
        srv = MultiModelServer(cfg, merged, slots_per_instance=2,
                               max_context=64, mesh=mesh, faults=inj)
        inj.arm()

        async def main():
            engine = AsyncEngine(srv)
            sup = Supervisor(engine, backoff_base_s=0.001)
            sup.start()
            async def client(r):
                s = await engine.submit(r)
                toks = [t async for t in s]
                res = await s.result()
                return s.request_id, toks, res
            out = await asyncio.gather(*(client(r) for r in mk_reqs()))
            await engine.aclose()
            return out, sup

        out, sup = asyncio.run(main())
        got = {rid: (toks, res.status) for rid, toks, res in out}
        assert sup.restarts == 1, sup.snapshot()
        assert got == want, (got, want)
        print("mesh crash recovery bit-identical OK")
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=REPO, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert "mesh crash recovery bit-identical OK" in r.stdout
