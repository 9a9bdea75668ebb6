"""chip_smoke.py rehearsed on the CPU.

The script itself runs only on a TPU; here its phases are driven from
the test on a small city, with the server on the CPU backend: the same
server command, config shape, requests, oracle comparison, engine checks
and shutdown the chip run makes.
"""

import importlib.util
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("chips", [1, 4])
def test_phases_on_cpu(smoke, tmp_path, capsys, chips):
    # chips=4: the --chips 4 path (dp2 x gp2, tiered table) on four of
    # the eight virtual CPU devices
    conf = smoke.server_config(smoke.write_network(str(tmp_path), grid=8),
                               smoke.MESH_MATCHER if chips == 4 else None)
    cohorts = (("short", 64, 6), ("med", 256, 2), ("long", 1024, 1))

    def prepare():
        oracle = smoke.build_oracle(conf)
        traces, sessions = smoke.make_traces(
            oracle.arrays, cohorts=cohorts, vehicles=2, steps=3)
        return traces, sessions, oracle

    out = smoke.run(str(tmp_path), conf, dict(os.environ), "cpu", chips,
                    prepare)
    ans, eng = out["answers"], out["engine"]
    assert ans["traces"] == 9 and ans["session_steps"] == 6
    assert ans["identical_records"] >= 0.95 * ans["traces"]
    assert eng["platform"] == "cpu" and eng["count"] == chips
    assert eng["mesh"] == ({"dp": 2, "gp": 2} if chips == 4
                           else {"dp": 1, "gp": 1})
    assert set(eng["placement"]) == {"graph", "ubodt", "session_arena"}
    assert eng["request_path_compiles"] == 0
    assert eng["watchdog_trips"] == 0 and eng["degraded_requests"] == 0
    assert eng["warmup_seconds"] > 0
    printed = capsys.readouterr().out
    assert "answers:" in printed and "engine:" in printed


def test_refuses_a_cpu_engine(smoke):
    statusz = {"device": {"platform": "cpu", "device_kind": "cpu",
                          "count": 1, "placement": {}}, "metrics": {}}
    with pytest.raises(smoke.SmokeFailure, match="not 'tpu'"):
        smoke.check_engine(statusz, "tpu", 1)
    statusz["device"].update(platform="tpu", placement={"ubodt": [0]})
    with pytest.raises(smoke.SmokeFailure, match="not on 4"):
        smoke.check_engine(dict(statusz, device=dict(
            statusz["device"], count=4)), "tpu", 4)


def test_main_without_a_tpu_prints_no_result(smoke, capsys):
    # the device probe child inherits JAX_PLATFORMS=cpu (conftest): it
    # finds no TPU, so the script stops before building anything
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    for line in out.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
