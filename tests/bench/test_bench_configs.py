"""Each configuration file resolves to its published widths, cut only
where ``reduced`` says and only as its model family allows
(``bench/arch/<arch>.py``), and builds the program's ModelConfig."""
import glob
import json
import os

import pytest

from benchtiny import REPO

from bench.harness import Registry

FILES = sorted(glob.glob(os.path.join(REPO, "bench", "configs", "*.json")))


def check_published(c: dict, family):
    """The configuration as run against its published config, by the
    family's map of published keys (``PUBLISHED``), the sizes it implies
    (``implied``) and the keys ``reduced`` may cut (``CUTS``, each only
    downwards); widths are never cut."""
    pub, m = c["published"], c["model"]
    assert set(c["reduced"]) <= family.CUTS <= set(family.PUBLISHED)
    for k, mk in family.PUBLISHED.items():
        if k not in pub:
            continue
        if k in c["reduced"]:
            assert 0 < m[mk] < pub[k], k
        else:
            assert m[mk] == pub[k], k
    for mk, v in family.implied(pub).items():
        assert m[mk] == v, mk
    assert m["dtype"] == m["param_dtype"] == pub["torch_dtype"]


@pytest.mark.parametrize("path", FILES, ids=os.path.basename)
def test_published_widths(path):
    from repro.config import ModelConfig
    c = json.load(open(path))
    m = c["model"]
    assert c["name"] == os.path.basename(path)[:-5] == m["name"]
    assert c["source"].startswith("https://")
    check_published(c, Registry([REPO]).arch(m["arch"]))
    ModelConfig(**m)


def test_benchmark_names_each_config_file():
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    for c in bench["configs"]:
        f = json.load(open(os.path.join(REPO, c["file"])))
        assert f["name"] == c["name"] and f["source"] == c["source"]
        assert f["reduced"] == c["reduced"]
