"""A whole run of the harness on the CPU at a tiny size: everything after
the look for the chip, against the program's reduced configuration. The
sealed case is the path the sealed cells time (ColoE weights through
``sealed_matmul``, the in-graph embedding decrypt, a sealed paged cache
with MACs verified on every read); its Pallas kernels run in interpret
mode here, so it takes about two minutes."""
import pytest

from helpers import TINY_CONFIG, run_tiny

# granite's reduced configuration ties the output head to the embedding
TINY_GRANITE = dict(TINY_CONFIG, model_id="granite_3_2b",
                    tie_word_embeddings=True)
TINY_SEALED = dict(TINY_CONFIG, seal="full")


@pytest.mark.parametrize("config", [TINY_CONFIG, TINY_GRANITE, TINY_SEALED],
                         ids=["internlm2_reduced", "granite_reduced",
                              "internlm2_reduced_sealed"])
def test_tiny_run_is_correct_and_reports_its_metrics(tmp_path, monkeypatch,
                                                     config):
    res = run_tiny(tmp_path, monkeypatch, config=config)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] > 0 and res["failed"] == 0
    m = res["metrics"]
    assert set(m) == {"itl_p95_ms", "output_tok_s", "setup_s"}
    assert all(v["value"] > 0 for v in m.values())
    assert res["checks"]["compared_tokens"]["value"] >= 100
    assert res["device"]["platform"] == "cpu"
