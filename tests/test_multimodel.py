"""Multi-tenant serving (docs/SERVING.md "Multi-tenant serving").

The multi-model cache contract under test:

  * model-id routing is bitwise: every tenant serves exactly its own
    file-loaded ``Booster.predict``, through the registry, the stacked
    dispatch path, HTTP ``/predict`` and ``/explain``;
  * same-shape tenants SHARE compiled programs — mixed-tenant stacked
    dispatch after warmup traces nothing new;
  * LRU eviction under the HBM byte budget drops only device arrays:
    readmission rebuilds from the manifest-verified file (a tampered
    file is refused), in-flight requests pinned to an evicting model
    drain on their old reference (the hot-reload drain contract,
    extended to the evict path);
  * per-model SLO/drift isolation: one tenant's burn or poisoned reload
    names only that tenant in ``/ready``; siblings stay green;
  * fleet promotion is keyed ``(model_id, generation)``: per-tenant
    pointer files with independent counters, filtered history, and
    tenant-scoped rollback.
"""
import http.client
import json
import os
import shutil
import threading

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.serving import (MicroBatcher, MultiModelRegistry,
                                  ServingApp, parse_model_roster)
from lightgbm_tpu.telemetry import recompile_counts


def _make_data(seed=7, n=500):
    rs = np.random.RandomState(seed)
    X = rs.randn(n, 6)
    X[:, 4] = rs.randint(0, 9, n)
    X[rs.rand(n) < 0.15, 0] = np.nan
    y = ((X[:, 1] > 0) ^ (X[:, 4] == 3)).astype(np.float64)
    return X, y


def _train_to_file(path, seed=3):
    X, y = _make_data(seed)
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "min_data_in_leaf": 5, "seed": seed}
    ds = lgb.Dataset(X, label=y, categorical_feature=[4])
    bst = lgb.train(params, ds, num_boost_round=6)
    bst.save_model(str(path))
    return X


@pytest.fixture(scope="module")
def tenants(tmp_path_factory):
    """(paths, X, refs) — three same-shape tenants plus a replacement
    candidate for beta; references are FILE-loaded boosters (the bytes
    the server actually serves)."""
    td = tmp_path_factory.mktemp("multimodel")
    paths, refs = {}, {}
    X = None
    for i, mid in enumerate(("alpha", "beta", "gamma")):
        p = td / f"{mid}.txt"
        X = _train_to_file(p, seed=3 + i)
        paths[mid] = str(p)
        refs[mid] = lgb.Booster(model_file=str(p))
    p2 = td / "beta_v2.txt"
    _train_to_file(p2, seed=31)
    paths["beta_v2"] = str(p2)
    refs["beta_v2"] = lgb.Booster(model_file=str(p2))
    return paths, X, refs


@pytest.fixture(scope="module")
def multiapp(tenants):
    """One warmed multi-tenant ServingApp shared by the HTTP tests."""
    paths, X, refs = tenants
    roster = {m: paths[m] for m in ("alpha", "beta", "gamma")}
    app = ServingApp("", models=roster, port=0, max_batch=32,
                     max_delay_ms=1.0, queue_size=256,
                     explain_max_batch=16).start()
    yield app, X, refs
    app.shutdown(drain=True)


def _post(host, port, path, obj, timeout=15):
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request("POST", path, json.dumps(obj),
                     {"Content-Type": "application/json"})
        r = conn.getresponse()
        return r.status, json.loads(r.read())
    finally:
        conn.close()


def _get(host, port, path, timeout=15):
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request("GET", path)
        r = conn.getresponse()
        return r.status, json.loads(r.read())
    finally:
        conn.close()


# ---------------------------------------------------------------------------
# roster + config
# ---------------------------------------------------------------------------

def test_parse_model_roster():
    r = parse_model_roster("a=/models/a.txt, b=/models/b.txt")
    assert list(r) == ["a", "b"]
    assert parse_model_roster({"x": "p"}) == {"x": "p"}
    for bad in ("justapath", "a=", "=p", "a=p,a=q", "bad id=p", ""):
        with pytest.raises(lgb.LightGBMError):
            parse_model_roster(bad)


def test_config_roster_validation(tenants):
    from lightgbm_tpu.config import Config
    paths, _, _ = tenants
    spec = f"a={paths['alpha']},b={paths['beta']}"
    cfg = Config.from_params({"serve_models": spec,
                              "serve_default_model": "b"})
    assert cfg.serve_models == spec
    # alias
    cfg = Config.from_params({"model_roster": spec})
    assert cfg.serve_models == spec
    with pytest.raises(lgb.LightGBMError):
        Config.from_params({"serve_models": "nope"})
    with pytest.raises(lgb.LightGBMError, match="default"):
        Config.from_params({"serve_models": spec,
                            "serve_default_model": "zz"})
    with pytest.raises(lgb.LightGBMError):
        Config.from_params({"serve_models": spec,
                            "serve_hbm_budget_mb": -1})


# ---------------------------------------------------------------------------
# routing + shared-program stacked dispatch
# ---------------------------------------------------------------------------

def test_multi_registry_routing_bitwise(multiapp):
    app, X, refs = multiapp
    reg = app.registry
    for mid in ("alpha", "beta", "gamma"):
        got = reg.current(mid).raw_scores(X[:9])
        want = refs[mid].predict(X[:9], raw_score=True)
        assert np.array_equal(got, want), mid
    with pytest.raises(lgb.LightGBMError, match="unknown model_id"):
        reg.current("nope")


def test_stacked_dispatch_zero_recompiles_bitwise(multiapp):
    """Mixed-tenant windows dispatch as ONE stacked program; after the
    boot warmup no bucket/slot combination traces anything new."""
    app, X, refs = multiapp
    reg = app.registry
    # prime: one grouped window so any lazy path is already traced
    jobs = [(reg.current(m), X[:8]) for m in ("alpha", "beta", "gamma")]
    reg.raw_scores_grouped(jobs)
    before = dict(recompile_counts())
    for rows in (X[:3], X[:8], X[10:26]):
        jobs = [(reg.current(m), rows) for m in ("alpha", "beta", "gamma")]
        outs = reg.raw_scores_grouped(jobs)
        for (model, r), got in zip(jobs, outs):
            want = refs[model.model_id].predict(r, raw_score=True)
            assert np.array_equal(got, want), model.model_id
    after = dict(recompile_counts())
    assert after == before, f"stacked dispatch recompiled: {before} -> {after}"


# ---------------------------------------------------------------------------
# LRU eviction + manifest-verified readmission
# ---------------------------------------------------------------------------

def test_lru_evict_readmit_bitwise(tenants, tmp_path):
    paths, X, refs = tenants
    local = {m: str(tmp_path / f"{m}.txt") for m in ("alpha", "beta")}
    for m, p in local.items():
        shutil.copy(paths[m], p)
        sidecar = paths[m] + ".quality.json"
        if os.path.exists(sidecar):
            shutil.copy(sidecar, p + ".quality.json")
    reg = MultiModelRegistry(local, max_batch=8, warmup=False)
    one = reg.current("alpha").device_bytes()
    reg.budget_bytes = int(one * 1.5)    # room for ONE resident model
    reg.current("beta")                  # readmits beta, evicts alpha
    st = reg.stats()
    assert st["cache"]["resident"] == ["beta"]
    assert reg.evictions >= 1
    # readmission rebuilds from the file and stays bitwise
    got = reg.current("alpha").raw_scores(X[:7])
    assert np.array_equal(got, refs["alpha"].predict(X[:7], raw_score=True))
    assert reg.readmissions >= 1
    assert reg.stats()["cache"]["resident"] == ["alpha"]
    # a tampered file is refused at readmission (manifest re-verify)
    reg.current("beta")                  # beta resident, alpha evicted
    with open(local["alpha"], "r+") as fh:
        data = fh.read()
        fh.seek(0)
        fh.truncate()
        fh.write(data[: len(data) // 2])
    with pytest.raises(lgb.LightGBMError):
        reg.current("alpha")
    # beta is untouched by alpha's corruption
    got = reg.current("beta").raw_scores(X[:7])
    assert np.array_equal(got, refs["beta"].predict(X[:7], raw_score=True))


def test_evict_path_inflight_drain(multiapp):
    """The hot-reload drain contract on the EVICT path: requests pinned
    at submit drain bitwise on their old reference while the tenant is
    evicted and readmitted under traffic."""
    app, X, refs = multiapp
    b = MicroBatcher(app.registry, max_batch=32, max_delay_ms=1.0,
                     queue_size=256).start()
    stop = threading.Event()
    errs, out = [], []

    def client(seed):
        rs = np.random.RandomState(seed)
        while not stop.is_set():
            s = rs.randint(0, 400)
            m = int(rs.choice([1, 3, 7]))
            try:
                f = b.submit(X[s:s + m], raw_score=True, model_id="gamma")
                out.append((s, m, f.result(timeout=10)))
            except Exception as e:  # noqa: BLE001 — collected for assert
                errs.append(e)

    threads = [threading.Thread(target=client, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    try:
        for _ in range(5):
            app.registry.tenant("gamma").evict()   # mid-traffic eviction
            stop.wait(0.05)
    finally:
        stop.set()
        for t in threads:
            t.join(10)
        b.stop()
    assert not errs, errs[:3]
    assert len(out) > 10
    want = refs["gamma"].predict(X[:410], raw_score=True)
    for s, m, res in out:
        assert res.model_id == "gamma"
        assert np.array_equal(res.values, want[s:s + m]), f"rows {s}:{s+m}"
    assert app.registry.tenant("gamma").evictions >= 5


# ---------------------------------------------------------------------------
# HTTP: /predict + /explain routing
# ---------------------------------------------------------------------------

def test_http_model_id_routing_bitwise(multiapp):
    app, X, refs = multiapp
    for mid in ("alpha", "beta", "gamma"):
        code, obj = _post(app.host, app.port, "/predict",
                          {"rows": X[:11].tolist(), "model_id": mid})
        assert code == 200
        assert obj["model_id"] == mid
        assert np.array_equal(np.asarray(obj["predictions"]),
                              refs[mid].predict(X[:11]))
    # default tenant: first roster entry
    code, obj = _post(app.host, app.port, "/predict",
                      {"rows": X[:4].tolist()})
    assert code == 200
    assert np.array_equal(np.asarray(obj["predictions"]),
                          refs["alpha"].predict(X[:4]))
    code, obj = _post(app.host, app.port, "/predict",
                      {"rows": X[:4].tolist(), "model_id": "nope"})
    assert code == 400
    assert "unknown model_id" in obj["error"]


def test_http_explain_pred_contrib_contract(multiapp):
    """/explain returns per-feature contributions + expected value,
    bitwise equal to ``Booster.predict(pred_contrib=True)``."""
    app, X, refs = multiapp
    for mid, m in (("alpha", 5), ("beta", 3)):
        code, obj = _post(app.host, app.port, "/explain",
                          {"rows": X[:m].tolist(), "model_id": mid})
        assert code == 200, obj
        assert obj["model_id"] == mid
        want = refs[mid].predict(X[:m], pred_contrib=True)
        assert np.array_equal(np.asarray(obj["contributions"]), want), mid
    # explain lane surfaces its own counters
    code, st = _get(app.host, app.port, "/stats")
    assert code == 200
    assert st["explain"]["served"] >= 2


# ---------------------------------------------------------------------------
# per-model SLO / degradation isolation
# ---------------------------------------------------------------------------

def test_per_model_slo_isolation(multiapp):
    """One tenant's error-budget burn names only that tenant in /ready;
    siblings stay green (the isolation contract)."""
    app, X, refs = multiapp
    mon = app.slo_by_model["beta"]
    try:
        for _ in range(60):
            mon.record(500, 5.0)
        mon.tick()
        code, obj = _get(app.host, app.port, "/ready")
        assert code == 200
        models = obj["models"]
        assert "slo_alert" in models["beta"]
        assert "slo_alert" not in models["alpha"]
        assert "slo_alert" not in models["gamma"]
        assert "model beta" in obj.get("degraded", "")
        assert "model alpha" not in obj.get("degraded", "")
    finally:
        # drain the burn so later tests see a clean monitor
        for _ in range(2000):
            mon.record(200, 1.0)
        mon.tick()


def test_per_model_drift_isolation(multiapp):
    """A drift alert on one tenant's quality monitor marks only that
    tenant's /ready record; sibling tenants carry no drift_alert."""
    app, X, refs = multiapp
    q = app.quality_by_model.get("gamma")
    if q is None:
        pytest.skip("quality monitors disabled in this build")
    q.alerting = True
    try:
        code, obj = _get(app.host, app.port, "/ready")
        assert code == 200
        models = obj["models"]
        assert models["gamma"].get("drift_alert") is True
        assert "drift_alert" not in models["alpha"]
        assert "drift_alert" not in models["beta"]
        assert "model gamma" in obj.get("degraded", "")
        assert "model alpha" not in obj.get("degraded", "")
    finally:
        q.alerting = False


def test_poisoned_reload_isolated_to_tenant(multiapp, tmp_path):
    """A truncated candidate for one tenant is refused registry-locally;
    the tenant keeps serving its old bytes and siblings never notice."""
    app, X, refs = multiapp
    bad = tmp_path / "poison.txt"
    data = open(app.registry.tenant("beta").current().path).read()
    bad.write_text(data[: len(data) // 2])
    code, obj = _post(app.host, app.port, "/reload",
                      {"path": str(bad), "model_id": "beta"})
    assert code in (400, 409)
    for mid in ("alpha", "beta", "gamma"):
        code, obj = _post(app.host, app.port, "/predict",
                          {"rows": X[:6].tolist(), "model_id": mid})
        assert code == 200
        assert np.array_equal(np.asarray(obj["predictions"]),
                              refs[mid].predict(X[:6])), mid
    # model_id reload without multi-tenant serving is a structured 400
    code, obj = _post(app.host, app.port, "/reload",
                      {"path": str(bad), "model_id": "zz"})
    assert code in (400, 409)


def test_tenant_reload_leaves_siblings_bitwise(multiapp, tenants):
    """Promotion of one tenant (registry-local /reload) swaps only that
    tenant; sibling responses stay bitwise across the swap."""
    app, X, refs = multiapp
    paths, _, _ = tenants
    pre = {}
    for mid in ("alpha", "gamma"):
        _, obj = _post(app.host, app.port, "/predict",
                       {"rows": X[:9].tolist(), "model_id": mid})
        pre[mid] = np.asarray(obj["predictions"])
    code, obj = _post(app.host, app.port, "/reload",
                      {"path": paths["beta_v2"], "model_id": "beta"})
    assert code == 200, obj
    assert obj.get("model_id") == "beta"
    _, obj = _post(app.host, app.port, "/predict",
                   {"rows": X[:9].tolist(), "model_id": "beta"})
    assert np.array_equal(np.asarray(obj["predictions"]),
                          refs["beta_v2"].predict(X[:9]))
    for mid in ("alpha", "gamma"):
        _, obj = _post(app.host, app.port, "/predict",
                       {"rows": X[:9].tolist(), "model_id": mid})
        assert np.array_equal(np.asarray(obj["predictions"]), pre[mid]), mid
    # restore beta for any later test using this module fixture
    code, _ = _post(app.host, app.port, "/reload",
                    {"path": paths["beta"], "model_id": "beta"})
    assert code == 200


# ---------------------------------------------------------------------------
# per-tenant promotion pointers (no fleet processes: pointer unit tests)
# ---------------------------------------------------------------------------

def test_per_tenant_pointer_keying(tenants, tmp_path):
    from lightgbm_tpu.serving.fleet import (generation_history,
                                            pointer_name, promote_pointer,
                                            read_pointer, rollback_pointer)
    paths, _, _ = tenants
    fdir = str(tmp_path)
    pa = promote_pointer(fdir, paths["alpha"], model_id="a")
    pb = promote_pointer(fdir, paths["beta"], model_id="b")
    flat = promote_pointer(fdir, paths["gamma"])
    # independent per-tenant generation counters
    assert pa["generation"] == 1 and pb["generation"] == 1
    assert flat["generation"] == 1
    assert pa["model_id"] == "a" and "model_id" not in flat
    assert os.path.exists(os.path.join(fdir, pointer_name("a")))
    p2 = promote_pointer(fdir, paths["beta_v2"], model_id="b")
    assert p2["generation"] == 2
    assert read_pointer(fdir, "a")["generation"] == 1    # sibling untouched
    assert read_pointer(fdir)["generation"] == 1         # flat untouched
    # history: interleaved trail, per-tenant filter
    assert [h["generation"] for h in generation_history(fdir, "b")] == [1, 2]
    assert len(generation_history(fdir)) == 4
    assert [h["generation"] for h in generation_history(fdir, "")] == [1]
    # tenant-scoped rollback (sibling + flat counters stay put)
    rb = rollback_pointer(fdir, reason="test", model_id="b")
    assert rb["generation"] == 1 and rb["rollback_from"] == 2
    assert read_pointer(fdir, "b")["path"] == paths["beta"]
    assert read_pointer(fdir, "a")["generation"] == 1
    with pytest.raises(lgb.LightGBMError):
        pointer_name("bad id")
    with pytest.raises(lgb.LightGBMError):
        rollback_pointer(fdir, model_id="a")   # no prior generation
