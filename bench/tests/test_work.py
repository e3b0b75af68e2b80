"""The work functions, against hand counts."""
from bench import work


def test_cnn_forward_macs():
    macs = work.cnn_layer_macs()
    assert macs == {'conv1': 28 * 28 * 20 * 25,           # 392,000
                    'conv2': 14 * 14 * 50 * 25 * 20,      # 4,900,000
                    'fc1': 7 * 7 * 50 * 128,              # 313,600
                    'fc2': 128 * 10}                      # 1,280
    assert work.cnn_forward_macs() == 5_606_880


def test_cnn_train_flops():
    # forward + weight grads of all layers + input grads of all but conv1
    assert work.cnn_train_flops_per_image() == 2 * (3 * 5_606_880 - 392_000)
    # the paper round: 100 clients x 24 batches x 40 images x 5 epochs
    flops = work.supervised_round_flops(100, 24, 40, 5,
                                        work.cnn_train_flops_per_image())
    assert flops == 480_000 * 32_857_280
    assert 15.7e12 < flops < 15.8e12


def test_dense_aggregate_bytes():
    m, n = 100, 342_016
    # cache, trained, new cache: 3 x 100 x 342,016 f32; global in and out;
    # three masks of 100 bytes and 100 f32 weights
    assert work.dense_aggregate_bytes(m, n) == \
        3 * 100 * 342_016 * 4 + 2 * 342_016 * 4 + 3 * 100 + 400
    assert work.dense_aggregate_bytes(m, n) == 413_156_028


def test_row_kernel_bytes():
    k, n = 251, 1_400_000
    assert work.rows_bytes(k, n) == 2 * 251 * 1_400_000 * 4
    assert work.quantize_bytes(k, n) == \
        251 * 1_400_000 * 5 + 251 * (1_400_000 // 128) * 4
    # int8 uploads + scales, cache entries read and written, global and
    # aggregate in and out
    assert work.tier_q8_bytes(251, 180, n) == (
        251 * 1_400_000 + 251 * 10_937 * 4 + 2 * 180 * 1_400_000 * 4
        + 4 * 1_400_000 * 4)
    assert work.round_state_bytes(150, 180, n) == \
        (150 + 180) * 1_400_000 * 4 + 4 * 1_400_000 * 4
