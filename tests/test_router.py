"""Placement router (§3.4 provider-side decisions)."""
import pytest

from repro.common.hardware import V5E
from repro.configs import get_config
from repro.serving.router import PlacementRouter, Slot


@pytest.fixture
def router():
    cfg = get_config("symbiosis-llama2-13b")
    return PlacementRouter(cfg, [Slot(0, free_hbm=10e9), Slot(1, free_hbm=10e9)], chip=V5E)


class TestRouting:
    def test_short_context_goes_gpu(self, router):
        p = router.route(context_len=2_000)
        assert p.mode == "gpu" and p.slot_id is not None

    def test_long_context_goes_hetero(self, router):
        p = router.route(context_len=262_144)
        assert p.mode == "hetero" and p.slot_id is None

    def test_mid_context_offloads(self, router):
        # 32k cache ~26 GB: too big for a 10 GB slot, too fast for CPU-only?
        p = router.route(context_len=32_768, latency_sensitive=False)
        assert p.mode in ("gpu_offload", "hetero")

    def test_hbm_accounting(self, router):
        p1 = router.route(context_len=4_000)
        assert p1.mode == "gpu"
        free_after = router.slots[p1.slot_id].free_hbm
        assert free_after < 10e9
        router.release(p1)
        assert router.slots[p1.slot_id].free_hbm == pytest.approx(10e9)

    def test_fleet_fills_then_spills(self, router):
        placements = [router.route(context_len=8_000) for _ in range(4)]
        modes = [p.mode for p in placements]
        assert modes[0] == "gpu"
        # eventually the 10 GB slots fill (8k cache ~6.5 GB each) and
        # requests spill to offload/CPU
        assert any(m != "gpu" for m in modes)

    def test_batch_not_double_counted(self, router):
        """Regression: route() used to check fits(need * batch) while
        cache_bytes(…, batch) already includes the batch factor, then
        commit() deducted only `need` — over-rejecting by batch× and
        desynchronizing the accounting."""
        cfg = get_config("symbiosis-llama2-13b")
        from repro.serving.kvcache import cache_bytes
        need = cache_bytes(cfg, 4_000, batch=4)
        # a slot that fits the true batch-4 footprint but not 4x it
        r = PlacementRouter(cfg, [Slot(0, free_hbm=need * 1.5)],
                            chip=V5E, host_free_bytes=0)
        p = r.route(context_len=4_000, batch=4)
        assert p.mode == "gpu" and p.cache_bytes == need

    def test_commit_release_round_trip(self, router):
        """commit() and release() must be exact inverses across all modes."""
        snapshot = ({sid: s.free_hbm for sid, s in router.slots.items()},
                    router.host_free)
        placements = [router.route(context_len=cl, batch=b,
                                   latency_sensitive=ls)
                      for cl, b, ls in [(2_000, 1, True), (4_000, 4, True),
                                        (32_768, 2, False), (262_144, 1, False)]]
        assert {p.mode for p in placements} >= {"gpu", "hetero"}
        for p in placements:
            router.release(p)
        assert router.host_free == pytest.approx(snapshot[1])
        for sid, s in router.slots.items():
            assert s.free_hbm == pytest.approx(snapshot[0][sid])

    def test_oom_raises(self):
        cfg = get_config("symbiosis-llama2-13b")
        r = PlacementRouter(cfg, [Slot(0, free_hbm=1e9)], chip=V5E, host_free_bytes=1e9)
        with pytest.raises(RuntimeError):
            r.route(context_len=500_000)


class TestChipTable:
    def test_router_prices_the_local_chip_or_refuses(self):
        """Without an explicit chip the router takes the local device's
        table entry; the CPU has none, so that is an error, not a v5e."""
        cfg = get_config("symbiosis-llama2-13b")
        with pytest.raises(ValueError, match="no hardware entry"):
            PlacementRouter(cfg, [Slot(0, free_hbm=1e9)])

    def test_chip_table_is_keyed_by_device_kind(self):
        from types import SimpleNamespace

        from repro.common.hardware import chip_for
        assert chip_for(SimpleNamespace(device_kind="TPU v5 lite")) is V5E
        with pytest.raises(ValueError):
            chip_for(SimpleNamespace(device_kind="TPU v4"))
