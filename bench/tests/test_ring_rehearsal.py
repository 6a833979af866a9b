"""The four-chip ring cell, rehearsed on four virtual CPU devices: a sound
run is correct, and one whose ring passes nothing on is not."""
import pytest

from bench.tests._proc import rehearse


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("ring")
    results = rehearse(
        tmp, "syn16.ring4.pairs.e03", 3000, "--fault", "no_exchange",
        "--fault", "altered_answer", devices=4,
    )
    sound = rehearse(tmp, "syn16.ring4.pairs.e03", 3000, devices=4)
    return {r["fault"]: r for r in results + sound}


def test_sound_ring_run(runs):
    out = runs[None]
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"ring_join_s", "setup_s"}
    # each join builds a new engine, whose ring program is built again
    # inside the window: loaded from the compile cache, or compiled where
    # it compiles too fast to be cached (the CPU here)
    w = out["window"]
    assert w["compiles"] + w["cache_loads"] >= w["joins"]


@pytest.mark.parametrize("fault", ["no_exchange", "altered_answer"])
def test_ring_fault_is_not_correct(runs, fault):
    out = runs[fault]
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())
