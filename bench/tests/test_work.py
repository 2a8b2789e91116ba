"""The solver pool's work, against a count made by hand."""

import work


def test_layer_of_three_qubits_in_groups_of_two():
    # groups [2, 1]: phase 6 + 8*4 + 8*2 = 54 flops per amplitude, 8 amps
    flops, nbytes = work.layer(3, 2)
    assert flops == 8 * 54
    assert nbytes == 8 * 20


def test_pool_batch_by_hand():
    m, n, p, steps, group, e_pad = 5, 3, 2, 4, 2, 3
    amps = 8
    layer_f, layer_b = amps * 54, amps * 20
    exp_f, exp_b = amps * 4, amps * 12
    cut_f, cut_b = amps * 3 * e_pad, amps * 4
    per_f = cut_f + steps * (3 * p * layer_f + exp_f) + p * layer_f + exp_f
    per_b = cut_b + steps * (3 * p * layer_b + exp_b) + p * layer_b + exp_b
    assert work.pool_batch(m, n, p, steps, group, e_pad) == (m * per_f,
                                                             m * per_b)


def test_a_twenty_qubit_layer_has_three_groups():
    flops, _ = work.layer(20, 7)
    assert flops == 2**20 * (6 + 8 * (128 + 128 + 64))
