"""Online inference serving (paddle_tpu.serving) acceptance suite.

Contracts under test: batched responses bit-identical to per-request
``CompiledModel.run()``; batch occupancy > 1 under concurrent load;
deadline-exceeded and overloaded requests shed with recorded degradation
events (and without hangs); hot reload swaps versions atomically behind
in-flight requests and rolls back on a warm-up fault armed through the
``PADDLE_TPU_FAULT_SPEC`` grammar; the ``paddle_tpu serve`` CLI verb
answers HTTP and exits cleanly on SIGTERM.
"""
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import resilience
from paddle_tpu.inference import ArtifactError
from paddle_tpu.serving import (DeadlineExceededError, InferenceService,
                                ModelUnavailableError, OverloadError,
                                ServingError, bucket_for, padding_buckets)

DIM = 6
ROWS = 4
OUT = 3


def _export(dirname, scale):
    """Export y = x @ W with W constant-filled by ``scale`` — outputs are
    predictable (row sums * scale), so v1/v2 artifacts are tellable."""
    with pt.scope_guard(pt.Scope()):
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            x = pt.layers.data("x", shape=[DIM], dtype="float32")
            w = pt.ParamAttr(
                name="serve_w",
                initializer=pt.initializer.ConstantInitializer(scale))
            out = pt.layers.fc(x, size=OUT, param_attr=w, bias_attr=False,
                               act=None)
        exe = pt.Executor(pt.CPUPlace())
        exe.run(startup)
        pt.inference.export_compiled(
            dirname, ["x"], [out], exe, main_program=main,
            example_feed={"x": np.zeros((ROWS, DIM), np.float32)})
    return dirname


@pytest.fixture(scope="module")
def art_v1(tmp_path_factory):
    return _export(str(tmp_path_factory.mktemp("serving") / "v1"), 0.5)


@pytest.fixture(scope="module")
def art_v2(tmp_path_factory):
    return _export(str(tmp_path_factory.mktemp("serving") / "v2"), 1.0)


@pytest.fixture(autouse=True)
def _clean_faults():
    resilience.reset()
    resilience.clear_events()
    yield
    resilience.reset()


def _feeds(n, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.rand(ROWS, DIM).astype(np.float32) for _ in range(n)]


def _expected(x, scale):
    return np.repeat(x.sum(axis=1, keepdims=True) * scale, OUT, axis=1)


# -- buckets ------------------------------------------------------------------

def test_padding_buckets():
    assert padding_buckets(8) == [1, 2, 4, 8]
    assert padding_buckets(6) == [1, 2, 4, 6]
    assert padding_buckets(1) == [1]
    assert bucket_for(3, [1, 2, 4, 8]) == 4
    assert bucket_for(1, [1, 2, 4]) == 1
    assert bucket_for(9, [1, 2, 4, 8]) == 8  # capped at max_batch


# -- batching: bit-identity + occupancy ---------------------------------------

def test_batched_bit_identical_and_occupancy(art_v1):
    feeds = _feeds(12, seed=1)
    model = pt.inference.load_compiled(art_v1)
    want = [np.asarray(model.run({"x": f})[0]) for f in feeds]
    with InferenceService(max_batch=4, batch_timeout_ms=50,
                          queue_depth=32) as svc:
        svc.load_model("m", art_v1)
        results = [None] * len(feeds)

        def worker(i):
            results[i] = svc.infer("m", {"x": feeds[i]})

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(len(feeds))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        st = svc.stats
    for i in range(len(feeds)):
        # the acceptance bar: BIT-identical to the offline run() path
        np.testing.assert_array_equal(results[i][0], want[i])
        np.testing.assert_allclose(results[i][0],
                                   _expected(feeds[i], 0.5), rtol=1e-4)
    assert st["completed"] == len(feeds)
    assert st["max_occupancy"] > 1           # coalescing really happened
    assert st["batches"] < len(feeds)
    assert st["batch_occupancy"] > 1.0
    assert st["latency_ms_p99"] >= st["latency_ms_p50"] > 0.0


def test_padded_bucket_stays_exact(art_v1):
    # 3 concurrent requests, max_batch=4 -> bucket 4, one padded row:
    # the pad is computed and discarded, live rows unaffected
    feeds = _feeds(3, seed=2)
    model = pt.inference.load_compiled(art_v1)
    want = [np.asarray(model.run({"x": f})[0]) for f in feeds]
    with InferenceService(max_batch=4, batch_timeout_ms=100,
                          queue_depth=32) as svc:
        svc.load_model("m", art_v1)
        results = [None] * 3
        threads = [threading.Thread(
            target=lambda i=i: results.__setitem__(
                i, svc.infer("m", {"x": feeds[i]}))) for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        st = svc.stats
    for got, w in zip(results, want):
        np.testing.assert_array_equal(got[0], w)
    if st["batches"] == 1:       # all three coalesced (the usual case)
        assert st["padded_rows"] == 1


def test_single_request_no_concurrency(art_v1):
    model = pt.inference.load_compiled(art_v1)
    f = _feeds(1, seed=3)[0]
    with InferenceService(max_batch=8, batch_timeout_ms=0,
                          queue_depth=8) as svc:
        svc.load_model("m", art_v1)
        got = svc.infer("m", {"x": f})
        np.testing.assert_array_equal(got[0],
                                      np.asarray(model.run({"x": f})[0]))
        assert svc.stats["batches"] == 1
        assert svc.stats["batch_occupancy"] == 1.0


# -- admission control --------------------------------------------------------

def test_deadline_exceeded_is_shed_not_hung(art_v1):
    with InferenceService(max_batch=4, batch_timeout_ms=0,
                          queue_depth=8) as svc:
        svc.load_model("m", art_v1)
        f = _feeds(1, seed=4)[0]
        # already-expired deadline: shed at dispatch, never served
        with pytest.raises(DeadlineExceededError):
            svc.infer("m", {"x": f}, deadline_ms=-1, timeout=30)
        # a sane deadline still serves
        out = svc.infer("m", {"x": f}, deadline_ms=30_000)
        assert np.asarray(out[0]).shape == (ROWS, OUT)
        assert svc.stats["shed_deadline"] == 1
    evs = resilience.events(kind="request_shed", site="serving.dispatch")
    assert evs and evs[0]["reason"] == "deadline"


def test_overload_is_shed_with_event(art_v1):
    # a slow device (delay fault at the dispatch edge) backs the queue
    # up into admission control; request queue_depth+1 is rejected NOW
    resilience.arm("serving.dispatch", action="delay", delay=0.3,
                   nth=1, times=None)
    svc = InferenceService(max_batch=1, batch_timeout_ms=0, queue_depth=2)
    try:
        svc.load_model("m", art_v1)
        feeds = _feeds(4, seed=5)
        first = svc.infer_async("m", {"x": feeds[0]})
        deadline = time.monotonic() + 5.0
        while svc._batcher.pending() and time.monotonic() < deadline:
            time.sleep(0.005)   # wait for it to enter the slow dispatch
        q1 = svc.infer_async("m", {"x": feeds[1]})
        q2 = svc.infer_async("m", {"x": feeds[2]})
        with pytest.raises(OverloadError):
            svc.infer("m", {"x": feeds[3]})
        assert svc.stats["shed_overload"] == 1
        resilience.disarm("serving.dispatch")
        for h in (first, q1, q2):       # the admitted ones still finish
            assert np.asarray(h.wait(timeout=30)[0]).shape == (ROWS, OUT)
    finally:
        svc.close()
    evs = resilience.events(kind="request_shed", site="serving.admission")
    assert evs and evs[0]["reason"] == "overload"


def test_dispatch_fault_fails_batch_not_service(art_v1):
    resilience.arm("serving.dispatch", action="raise", nth=1, times=1)
    with InferenceService(max_batch=4, batch_timeout_ms=0,
                          queue_depth=8) as svc:
        svc.load_model("m", art_v1)
        f = _feeds(1, seed=6)[0]
        with pytest.raises(resilience.FaultError):
            svc.infer("m", {"x": f}, timeout=30)
        # the dispatch loop survived the failed batch
        out = svc.infer("m", {"x": f}, timeout=30)
        assert np.asarray(out[0]).shape == (ROWS, OUT)
        assert svc.stats["failed"] == 1
    assert resilience.events(kind="batch_failed", site="serving.dispatch")


def test_closed_service_rejects_and_fails_queued(art_v1):
    svc = InferenceService(max_batch=4, batch_timeout_ms=0, queue_depth=8)
    svc.load_model("m", art_v1)
    svc.close()
    with pytest.raises(ServingError):
        svc.infer("m", {"x": _feeds(1)[0]})


def test_unknown_model_and_missing_feed(art_v1):
    with InferenceService(max_batch=2, batch_timeout_ms=0,
                          queue_depth=8) as svc:
        with pytest.raises(ModelUnavailableError):
            svc.infer("nope", {"x": _feeds(1)[0]})
        svc.load_model("m", art_v1)
        with pytest.raises(ValueError, match="missing"):
            svc.infer("m", {"y": _feeds(1)[0]})


# -- registry: hot reload + rollback ------------------------------------------

def test_hot_reload_swaps_behind_in_flight_requests(art_v1, art_v2):
    feeds = _feeds(40, seed=7)
    with InferenceService(max_batch=4, batch_timeout_ms=1,
                          queue_depth=64) as svc:
        assert svc.load_model("m", art_v1).version == 1
        outputs, errors = [], []
        stop = threading.Event()

        def client():
            i = 0
            while not stop.is_set():
                f = feeds[i % len(feeds)]
                try:
                    outputs.append((f, svc.infer("m", {"x": f},
                                                 timeout=30)[0]))
                except Exception as e:      # no request may fail mid-swap
                    errors.append(e)
                i += 1

        threads = [threading.Thread(target=client) for _ in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.1)                     # in-flight traffic on v1
        entry = svc.reload_model("m", art_v2)
        time.sleep(0.1)                     # traffic continues on v2
        stop.set()
        for t in threads:
            t.join()
        assert entry.version == 2
        assert not errors
        assert len(outputs) > 0
        for f, out in outputs:
            w1, w2 = _expected(f, 0.5), _expected(f, 1.0)
            ok = (np.allclose(out, w1, rtol=1e-4)
                  or np.allclose(out, w2, rtol=1e-4))
            assert ok, "response matches neither version's weights"
        # after the swap, fresh requests are served by v2
        f = feeds[0]
        np.testing.assert_allclose(svc.infer("m", {"x": f})[0],
                                   _expected(f, 1.0), rtol=1e-4)
        assert svc.stats["models"]["m"] == 2
    assert resilience.events(kind="model_loaded", site="serving.reload")


def test_reload_rollback_on_warmup_fault(art_v1, art_v2, monkeypatch):
    """The acceptance chaos path: a warm-up fault armed through the
    PADDLE_TPU_FAULT_SPEC grammar makes the reload fail — the previous
    version keeps serving and the rollback is a recorded event."""
    monkeypatch.setenv("PADDLE_TPU_FAULT_SPEC",
                       "serving.reload:raise:nth=1,times=1")
    with InferenceService(max_batch=2, batch_timeout_ms=0,
                          queue_depth=8) as svc:
        svc.load_model("m", art_v1, warm=False)   # load before arming
        resilience.load_fault_spec()               # arm from the env var
        with pytest.raises(resilience.FaultError):
            svc.reload_model("m", art_v2)
        # rollback: v1 still published and still serving v1 weights
        assert svc.registry.get("m").version == 1
        f = _feeds(1, seed=8)[0]
        np.testing.assert_allclose(svc.infer("m", {"x": f})[0],
                                   _expected(f, 0.5), rtol=1e-4)
        evs = resilience.events(kind="reload_rollback",
                                site="serving.reload")
        assert evs and evs[0]["kept_version"] == 1
        # the fault window has passed: the next reload goes through
        assert svc.reload_model("m", art_v2).version == 2
        np.testing.assert_allclose(svc.infer("m", {"x": f})[0],
                                   _expected(f, 1.0), rtol=1e-4)


def test_initial_load_failure_is_readable(tmp_path):
    with InferenceService(max_batch=2, batch_timeout_ms=0,
                          queue_depth=8) as svc:
        with pytest.raises(ArtifactError, match="does not exist"):
            svc.load_model("m", str(tmp_path / "nope"))
        with pytest.raises(ModelUnavailableError):
            svc.infer("m", {"x": _feeds(1)[0]})


def test_warmup_pretriggers_every_bucket(art_v1):
    with InferenceService(max_batch=4, batch_timeout_ms=0,
                          queue_depth=8) as svc:
        entry = svc.load_model("m", art_v1)
        assert entry.warm_buckets == (1, 2, 4)
        assert entry.warmup_ms > 0.0
        model = entry.model
        # every scan bucket is compiled: serving depths 2 and 4 add no
        # new traces (bucket 1 uses run(), not the scan)
        before = model._scan_call._cache_size()
        feeds = _feeds(4, seed=9)
        stacked2 = {"x": np.stack(feeds[:2])}
        stacked4 = {"x": np.stack(feeds)}
        model.run_many(stacked2)
        model.run_many(stacked4)
        assert model._scan_call._cache_size() == before


# -- metrics ------------------------------------------------------------------

def test_stats_and_profiler_serving_section(art_v1, tmp_path):
    from paddle_tpu import profiler
    profiler.reset_serving_counters()
    with InferenceService(max_batch=4, batch_timeout_ms=0,
                          queue_depth=8) as svc:
        svc.load_model("m", art_v1)
        for f in _feeds(5, seed=10):
            svc.infer("m", {"x": f})
        st = svc.stats
    assert st["requests"] == 5 and st["completed"] == 5
    assert st["batches"] >= 1
    assert st["latency_ms_p50"] > 0 and st["queue_wait_ms_p99"] >= 0
    ctr = profiler.serving_counters()
    assert ctr["requests"] == 5 and ctr["batches"] >= 1
    art = profiler.write_timeline(str(tmp_path / "timeline.json"))
    assert art["serving"]["requests"] == 5


# -- HTTP front end -----------------------------------------------------------

def _post(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode("utf-8"),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as r:
        return r.status, json.loads(r.read())


def test_http_endpoint(art_v1, art_v2):
    from paddle_tpu.serving import make_server
    with InferenceService(max_batch=4, batch_timeout_ms=1,
                          queue_depth=16) as svc:
        svc.load_model("m", art_v1)
        server = make_server(svc, port=0)
        port = server.server_address[1]
        t = threading.Thread(target=server.serve_forever, daemon=True)
        t.start()
        base = "http://127.0.0.1:%d" % port
        try:
            f = _feeds(1, seed=11)[0]
            code, resp = _post(base + "/v1/models/m:predict",
                               {"inputs": {"x": f.tolist()}})
            assert code == 200 and resp["version"] == 1
            np.testing.assert_allclose(
                np.asarray(resp["outputs"][0], np.float32),
                _expected(f, 0.5), rtol=1e-4)

            with urllib.request.urlopen(base + "/healthz",
                                        timeout=30) as r:
                health = json.loads(r.read())
            assert health["ok"] and "m" in health["models"]
            with urllib.request.urlopen(base + "/statz", timeout=30) as r:
                stats = json.loads(r.read())
            assert stats["requests"] >= 1

            # error mapping: wrong shape -> 400, unknown model -> 404
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(base + "/v1/models/m:predict",
                      {"inputs": {"x": [[1.0] * DIM]}})
            assert ei.value.code == 400
            assert "shape" in json.loads(ei.value.read())["error"]
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(base + "/v1/models/ghost:predict",
                      {"inputs": {"x": f.tolist()}})
            assert ei.value.code == 404

            # hot reload over HTTP; bad dirname -> 409 + kept version
            code, resp = _post(base + "/v1/models/m:reload",
                               {"dirname": art_v2})
            assert code == 200 and resp["version"] == 2
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(base + "/v1/models/m:reload",
                      {"dirname": art_v2 + "-missing"})
            assert ei.value.code == 409
            assert json.loads(ei.value.read())["serving_version"] == 2
            code, resp = _post(base + "/v1/models/m:predict",
                               {"inputs": {"x": f.tolist()}})
            assert resp["version"] == 2
            np.testing.assert_allclose(
                np.asarray(resp["outputs"][0], np.float32),
                _expected(f, 1.0), rtol=1e-4)
        finally:
            server.shutdown()
            server.server_close()


# -- the CLI verb -------------------------------------------------------------

def test_serve_cli_bad_artifact_exit_1(tmp_path, capsys):
    from paddle_tpu import cli
    rc = cli.main(["serve", str(tmp_path / "not-an-artifact")])
    assert rc == 1
    err = capsys.readouterr().err
    assert "does not exist" in err
    # partially-written artifact: every missing file is named
    broken = tmp_path / "broken"
    broken.mkdir()
    (broken / "__meta__.json").write_text("{}")
    rc = cli.main(["serve", str(broken)])
    assert rc == 1
    err = capsys.readouterr().err
    assert "__compiled__.stablehlo" in err and "__params__.pkl" in err


def test_serve_cli_http_and_sigterm(art_v1):
    """`paddle_tpu serve` starts, answers an HTTP request, and exits 0
    on SIGTERM — the full deployment loop as a subprocess."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo
    p = subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu", "serve", art_v1,
         "--name", "m", "--port", "0", "--batch_timeout_ms", "1"],
        cwd=repo, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)
    try:
        ready = {}

        def read_ready():
            ready["line"] = p.stdout.readline()

        t = threading.Thread(target=read_ready, daemon=True)
        t.start()
        t.join(timeout=240)
        assert ready.get("line"), "serve never printed its readiness line"
        info = json.loads(ready["line"])["serving"]
        assert info["model"] == "m" and info["version"] == 1

        f = _feeds(1, seed=12)[0]
        code, resp = _post(
            "http://%s:%d/v1/models/m:predict" % (info["host"],
                                                  info["port"]),
            {"inputs": {"x": f.tolist()}})
        assert code == 200
        np.testing.assert_allclose(
            np.asarray(resp["outputs"][0], np.float32),
            _expected(f, 0.5), rtol=1e-4)

        p.send_signal(signal.SIGTERM)
        out, err = p.communicate(timeout=60)
        assert p.returncode == 0, (out, err[-2000:])
        stopped = json.loads(out.strip().splitlines()[-1])
        assert stopped["serving_stopped"]["signal"] == signal.SIGTERM
        assert stopped["serving_stopped"]["stats"]["requests"] >= 1
    finally:
        if p.poll() is None:
            p.kill()
            p.communicate()
