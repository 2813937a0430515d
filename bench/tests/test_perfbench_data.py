"""The benchmark's generator and its roofline work counts, at small sizes."""

import numpy as np
import pytest

from bench import data, reference, roofline


def config(rows=2048, features=4096, nnz=40, workers=16):
    return {"name": "small",
            "dataset": {"rows": rows, "features": features,
                        "nnz_per_row": nnz, "min_nnz": 4,
                        "zipf_exponent": 0.8, "label_noise": 0.05},
            "cluster": {"workers": workers, "delay_model": "pareto"}}


@pytest.fixture(scope="module")
def small():
    return data.generate(config(), seed=2**31 + 7)


def test_mean_nonzeros_per_row(small):
    nnz = small.nnz_per_row()
    assert nnz.min() >= 4
    assert abs(nnz.mean() - 40) < 0.5  # Poisson(40) over 2,048 rows


def test_rows_have_unit_norm_and_distinct_features(small):
    norms = np.linalg.norm(small.vals.astype(np.float64), axis=1)
    np.testing.assert_allclose(norms, 1.0, rtol=1e-6)
    for cols, vals in zip(small.cols[:64], small.vals[:64]):
        live = cols[vals != 0]
        assert len(set(live.tolist())) == len(live)


def test_zipf_skew(small):
    counts = np.bincount(small.cols[small.vals != 0], minlength=small.d)
    # p_j ~ j^-0.8; drawn without replacement, feature j is in a row of 40
    # with odds of about 1 - (1 - p_j)^40.
    p = 1.0 / np.arange(1, small.d + 1) ** 0.8
    p /= p.sum()
    inclusion = 1 - (1 - p) ** 40
    expected = inclusion[0] / inclusion[9]
    assert counts[0] / counts[9] == pytest.approx(expected, rel=0.15)
    assert counts[:64].sum() > 10 * counts[-64:].sum()


def test_labels_are_signs_with_noise(small):
    assert set(np.unique(small.y).tolist()) == {-1.0, 1.0}
    assert reference.initial_gap(small) == 0.5


def test_same_seed_same_data_other_seed_other_data(small):
    again = data.generate(config(), seed=2**31 + 7)
    other = data.generate(config(), seed=2**31 + 8)
    np.testing.assert_array_equal(again.cols, small.cols)
    np.testing.assert_array_equal(again.vals, small.vals)
    np.testing.assert_array_equal(again.y, small.y)
    assert not np.array_equal(other.cols, small.cols)


def test_dense_layout_holds_the_triplets():
    cfg = config(rows=64, features=512, nnz=12, workers=4)
    sparse = data.generate(cfg, seed=3)
    K, n_k, d = data.shape_of(cfg)
    X, y = data.device_arrays(sparse, K, n_k)
    X = np.asarray(X).reshape(K * n_k, d)
    dense = np.zeros((K * n_k, d), np.float32)
    rows = np.repeat(np.arange(K * n_k), sparse.cols.shape[1])
    np.add.at(dense, (rows, sparse.cols.ravel()), sparse.vals.ravel())
    np.testing.assert_array_equal(X, dense)
    np.testing.assert_array_equal(np.asarray(y).ravel(), sparse.y)


def test_shape_refuses_rows_that_do_not_split():
    with pytest.raises(ValueError, match="do not split"):
        data.shape_of(config(rows=100, workers=16))


def test_roofline_terms_by_hand():
    assert roofline.local_solve(10, 5) == (300.0, 1000.0)
    assert roofline.message_filter(100, 7) == (200.0, 1256.0)
    # 2 payloads of 3 entries, 4 workers: 6 sums, 48 buffer and reply adds.
    assert roofline.server_apply(2, 4, 3) == (54.0, 576.0)
    assert roofline.certificate(10, 4, 6) == (100.0, 440.0)


def test_roofline_rounds_add_their_terms():
    ops, nbytes = roofline.group_round(K=4, n_k=2, d=100, nnz_row=5, B=2,
                                       T=2, H=2, k=7, eval_every=4)
    relaunches = (1 * 2 + 4) / 2
    w_ops = 6 * 5 * 2 + 2 * 100
    w_bytes = 20 * 5 * 2 + 12 * 100 + 8 * 7
    s_ops, s_bytes = roofline.server_apply(relaunches, 4, 7)
    c_ops, c_bytes = roofline.certificate(5 * 8, 8, 100)
    assert ops == pytest.approx(relaunches * w_ops + s_ops + c_ops / 4)
    assert nbytes == pytest.approx(relaunches * w_bytes + s_bytes
                                   + c_bytes / 4)
    ops, nbytes = roofline.lockstep_round(cells=3, K=4, n_k=2, d=100,
                                          nnz_row=5, H=2, eval_every=1)
    one = 4 * 60 + 2 * 4 * 10 + c_ops
    assert ops == pytest.approx(3 * one)


def test_peaks_of_an_unknown_device_are_an_error():
    assert roofline.least_seconds(197e12, 0, "TPU v5 lite") == 1.0
    assert roofline.least_seconds(0, 819e9, "TPU v5 lite") == 1.0
    with pytest.raises(KeyError, match="no peaks"):
        roofline.least_seconds(1, 1, "cpu")
