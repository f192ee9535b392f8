"""The kernels' needed-work counters at the cells' shapes."""

import pytest

import cell as cell_mod

GR = cell_mod.work_counter("gather_rerank")


def test_gather_rerank_counts_at_cell_shape():
    # knn-steady: a padded 64-query traversal batch, pool = L + 1.3 L + 8 =
    # 238 slots, k_eff = k * oversample = 40
    d = 768
    call = {"Q": 64, "D": d, "P": 238, "valid": 64 * 200, "k": 40, "N": 8192}
    ops, nbytes = GR.work(call)
    assert ops == 64 * 200 * (2 * d + 3)
    assert nbytes == 4 * (64 * 200 * d + 64 * d + 64 * 238 + 2 * 64 * 40)
    assert GR.peak(call) == "bf16_flops_per_s"


@pytest.mark.parametrize("impl", [
    {"N": 8192, "tile_q": 8, "tile_n": 128},
    {"N": 65536, "tile_q": 32, "tile_n": 512},
    {"N": 4194304},
])
def test_gather_rerank_work_does_not_depend_on_implementation(impl):
    """Counted work is what the rerank needs: the shard a kernel streams
    (``N``) and its tiles do not enter, so a true row gather and the
    whole-shard scan are held to one yardstick."""
    base = {"Q": 64, "D": 768, "P": 238, "valid": 12000, "k": 40}
    assert GR.work(dict(base, **impl)) == GR.work(dict(base, N=1))


def test_roofline_share_stays_under_100_at_the_bound():
    """A call timed at exactly its bound reads 100%; any real time reads less."""
    import roofline

    peaks = cell_mod.peaks("TPU v5 lite")
    call = {"op": "gather_rerank", "counter": "gather_rerank", "Q": 64, "D": 768,
            "P": 238, "valid": 12000, "k": 40, "N": 8192}
    ops, nbytes = GR.work(call)
    bound_s = max(ops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])

    class Run:
        kernel_calls = [call]
        work = {"gather_rerank": GR}

        def __init__(self, t):
            self.t = t
            self.peaks = peaks

        def module_seconds(self, *names):
            return self.t

    assert roofline.share(Run(bound_s), "gather_rerank", ()) == pytest.approx(100.0)
    assert roofline.share(Run(10 * bound_s), "gather_rerank", ()) == pytest.approx(10.0)
    assert roofline.share(Run(0.0), "gather_rerank", ()) is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        cell_mod.peaks("TPU v9 imaginary")
    assert cell_mod.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
