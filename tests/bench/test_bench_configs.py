"""Each configuration file resolves to its published widths, cut only
where ``reduced`` says, and builds the program's ModelConfig."""
import glob
import json
import os

import pytest

from benchtiny import REPO

FILES = sorted(glob.glob(os.path.join(REPO, "bench", "configs", "*.json")))
# published key -> key of the configuration as run; widths are never cut
WIDTHS = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads", "intermediate_size": "d_ff",
          "vocab_size": "vocab", "head_dim": "head_dim",
          "tie_word_embeddings": "tie_embeddings", "rope_theta": "rope_theta"}


@pytest.mark.parametrize("path", FILES, ids=os.path.basename)
def test_published_widths(path):
    from repro.config import ModelConfig
    c = json.load(open(path))
    pub, m = c["published"], c["model"]
    assert c["name"] == os.path.basename(path)[:-5] == m["name"]
    assert c["source"].startswith("https://")
    for k, mk in WIDTHS.items():
        if k in pub:
            assert m[mk] == pub[k], k
    if "head_dim" not in pub:
        assert m["head_dim"] == pub["hidden_size"] // pub["num_attention_heads"]
    if "num_hidden_layers" in c["reduced"]:
        assert 0 < m["n_layers"] < pub["num_hidden_layers"]
    else:
        assert m["n_layers"] == pub["num_hidden_layers"]
    assert set(c["reduced"]) <= {"num_hidden_layers"}
    assert m["dtype"] == m["param_dtype"] == pub["torch_dtype"]
    ModelConfig(**m)


def test_benchmark_names_each_config_file():
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    for c in bench["configs"]:
        f = json.load(open(os.path.join(REPO, c["file"])))
        assert f["name"] == c["name"] and f["source"] == c["source"]
        assert f["reduced"] == c["reduced"]
