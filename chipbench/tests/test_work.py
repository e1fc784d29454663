"""work.py against a count made by hand at toy shapes."""
import pytest

from chipbench import work

TOY = work.Shapes(token_rows=10, path_rows=6, target_rows=8, token_dim=2,
                  path_dim=3, code_dim=4, mu_bytes=2, nu_bytes=4)


def test_parameters():
    # 10*2 + 6*3 + 8*4 + (2+3+2)*4 + 4
    assert TOY.context_dim == 7
    assert TOY.parameters == 20 + 18 + 32 + 28 + 4 == 102


def test_train_step_by_hand():
    examples, contexts = 3, 5
    got = work.train_step(TOY, examples, contexts)
    encode = contexts * (2 * 7 * 4 + 4 + 2 * 4 + 3 + 2 * 4)   # 79 a context
    logits = examples * 2 * 4 * 8                              # 192
    forward = encode + logits
    flops = (3 * forward                       # forward + twice it backward
             + 2 * 3 * examples * 8            # cross-entropy, both passes
             + contexts * 7                    # scatter-adds
             + 12 * 102)                       # the Adam walk
    assert got['flops'] == flops == 3 * (395 + 192) + 144 + 35 + 1224
    hbm = (contexts * 12 + contexts * 7 * 4 * 3    # stream, gather, scatter
           + 2 * 8 * 4 * 4                         # tag table, fwd + bwd
           + 102 * 4                               # the dense gradient
           + 102 * (3 * 4 + 2 * 2 + 2 * 4))        # Adam
    assert got['hbm_bytes'] == hbm
    assert got['collective_bytes'] == 0


def test_data_parallel_adds_only_the_all_reduce():
    one = work.train_step(TOY, 3, 5)
    four = work.train_step(TOY, 3, 5, chips=4)
    assert four['flops'] == one['flops']
    assert four['hbm_bytes'] == one['hbm_bytes']
    assert four['collective_bytes'] == 2 * 3 / 4 * 102 * 4


def test_least_seconds_names_the_bound():
    peaks = {'flops_per_s_bf16': 100.0, 'hbm_bytes_per_s': 10.0,
             'ici_bytes_per_s': 1.0}
    floor = work.least_seconds({'flops': 200.0, 'hbm_bytes': 50.0,
                                'collective_bytes': 0.0}, peaks)
    assert floor['bound'] == 'hbm' and floor['seconds'] == 5.0
    floor = work.least_seconds({'flops': 200.0, 'hbm_bytes': 5.0,
                                'collective_bytes': 3.0}, peaks)
    assert floor['bound'] == 'ici' and floor['seconds'] == 3.0


def test_java14m_is_the_published_size():
    java14m = work.Shapes(1301248, 911488, 261248, 128, 128, 384)
    assert java14m.parameters == 383_697_280
    # the step is bound by memory traffic, as PERF.md section 5 found
    peaks = {'flops_per_s_bf16': 197e12, 'hbm_bytes_per_s': 819e9,
             'ici_bytes_per_s': 200e9}
    floor = work.least_seconds(work.train_step(java14m, 1024, 41_000), peaks)
    assert floor['bound'] == 'hbm'
    assert floor['seconds'] == pytest.approx(0.0125, rel=0.02)
