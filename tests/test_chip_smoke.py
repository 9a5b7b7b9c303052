"""chip_smoke.py's phases at a tiny config on the CPU (stream twins in place
of the compiled kernels), and its refusal to run without a TPU."""
import importlib.util
import pathlib
import sys

import jax
import numpy as np
import pytest

from conftest import tiny

_PATH = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
_spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
smoke = sys.modules["chip_smoke"] = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(smoke)

PLAN = smoke.Plan(prompt_lens=(6, 40, 9, 12, 20, 7), system_len=10,
                  question_len=4, max_new=4, page_block=4, max_seq=64,
                  pool_pages=16, train_seq=16, train_batch=2, train_steps=2)


@pytest.fixture(scope="module")
def cfg():
    return tiny(d_model=128, n_heads=4, n_kv_heads=2)


def test_one_chip_phases(cfg):
    out = smoke.run_one_chip(cfg, PLAN, seed=0, check_hlo=False)
    assert out["served"]["prefix_hits"] > 0
    assert out["served"]["decode_tokens"] == (
        (len(PLAN.prompt_lens) + 2) * (PLAN.max_new - 1))
    for method in smoke.METHODS:
        errs = out["logit_errors"][method]
        # float32 on the CPU: the engine path and the forward agree closely
        assert max(errs["prefill_err"], errs["decode_err"]) < 1e-4
        assert len(out["losses"][f"{method}-job"]) == PLAN.train_steps


def test_kernel_check_catches_a_wrong_adapter(cfg):
    """The logit comparison fails when the engine's logits come from
    another client's adapter."""
    spec = smoke.make_spec(cfg, PLAN)
    service, seen = smoke.build_service(spec, seed=0)
    reqs = smoke.make_requests(cfg, PLAN, seed=0)
    smoke.serve_phase(service, reqs)
    # client 0's first request now reports client 1's logits
    r0 = next(r for r in reqs if r.client_id == 0)
    r1 = next(r for r in reqs if r.client_id == PLAN.shared_client)
    seen[id(r0)] = seen[id(r1)]
    r0.prompt, r0.generated = r1.prompt, r1.generated
    with pytest.raises(smoke.SmokeFailure, match="lora logits"):
        smoke.kernel_check(spec, service.serving.base, service.serving.banks,
                           reqs, seen, PLAN)


def test_own_shards():
    x = jax.numpy.zeros((4, 2))
    assert not smoke.own_shards({"a": x}, 4)
    assert smoke.own_shards({"a": x}, 1)


def test_kernel_calls_reads_compiled_text():
    hlo = ('%a = custom-call(%x), custom_call_target="tpu_custom_call", '
           'metadata={op_name="jit(f)/sgmv/pallas_call"}\n'
           '%b = custom-call(%y), custom_call_target="tpu_custom_call", '
           'metadata={op_name="jit(f)/paged_decode_attn/pallas_call"}\n')
    assert smoke.kernel_calls(hlo, "sgmv") == 1
    assert smoke.kernel_calls(hlo, "paged_decode_attn") == 1
    assert smoke.kernel_calls(hlo, "paged_decode_attn_int8") == 0
    with pytest.raises(smoke.SmokeFailure):
        smoke.check_kernels_compiled({"compact_decode": hlo,
                                      "compact_prefill": "no kernels"})


def test_main_refuses_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) == 2
    out, err = capsys.readouterr()
    assert "needs a TPU" in err and "no phase was run" in err
    assert out == ""


def test_rel_err():
    a = np.array([1.0, -2.0, 4.0])
    assert smoke.rel_err(a, a) == 0.0
    assert smoke.rel_err(a + 0.4, a) == pytest.approx(0.1)
