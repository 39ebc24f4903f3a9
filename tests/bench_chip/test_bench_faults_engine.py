"""An engine cell's run with its timed path broken underneath comes out
not correct, once for each fault the cell can have; a sound run comes
out correct. Tiny widths on the CPU, the chip check skipped."""
from __future__ import annotations

import pytest

import bench_chip_util as u


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return u.checkout(tmp_path_factory.mktemp("engine"))


def test_sound_run_is_correct(root):
    res = u.run(root, "tiny.engine", seconds=1.0)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"]["tokens_per_s"]["value"] > 0
    assert res["checks"][-1]["name"] == "logit_gap_max"


@pytest.mark.parametrize("fault", ["token", "stale", "half"])
def test_broken_timed_path_is_not_correct(root, fault):
    res = u.run(root, "tiny.engine", seconds=1.0, fault=fault)
    assert not res["correct"]
    gap = res["checks"][-1]
    assert gap["name"] == "logit_gap_max" and not gap["ok"]


@pytest.fixture
def donated_decode(monkeypatch):
    """Every ``ServingEngine`` donates its cache to the decode step, as a
    program that stops copying the cache would."""
    import jax
    from repro.serve import engine as serve_engine
    init = serve_engine.ServingEngine.__init__

    def donating(self, *a, **k):
        init(self, *a, **k)
        self._decode_step = jax.jit(self._decode_step.__wrapped__,
                                    donate_argnums=(2,))
    monkeypatch.setattr(serve_engine.ServingEngine, "__init__", donating)


@pytest.mark.parametrize("fault", ["", "token", "stale", "half"])
def test_faults_hold_under_a_donated_decode_cache(root, donated_decode,
                                                  fault):
    """With the decode cache donated, a sound run is correct and each
    fault ends not correct, not in a deleted array."""
    res = u.run(root, "tiny.engine", seconds=1.0, fault=fault)
    gap = res["checks"][-1]
    assert gap["name"] == "logit_gap_max"
    assert res["correct"] == (not fault) == gap["ok"], res["checks"]
