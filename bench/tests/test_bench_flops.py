"""The work functions against counts made by hand."""
from bench.harness import flops


def test_resnet18_forward_at_32x32():
    # stem 3x3x3->64 at 32x32; stage 1: four 3x3 64->64 at 32x32;
    # stages 2-4 at 16, 8, 4: a strided 3x3 cin->w, three 3x3 w->w and a
    # 1x1 projection cin->w, all at the stage's map size
    macs = 32 * 32 * 27 * 64 + 4 * 32 * 32 * 9 * 64 * 64
    for size, cin, w in ((16, 64, 128), (8, 128, 256), (4, 256, 512)):
        macs += size * size * (9 * cin * w + 3 * 9 * w * w + cin * w)
    got = flops.resnet_forward_flops((2, 2, 2, 2), (64, 128, 256, 512), 32)
    assert got == 2 * macs == 1_110_835_200


def test_greedy_round_work_reads_the_pool_once():
    f, b = flops.greedy_round_work(49_000, 512, 1)
    assert f == 2 * 49_000 * 512
    # pool, min-dist in and out, one center and its index
    assert b == 4 * (49_000 * 512 + 2 * 49_000 + 512 + 1)
    _, bw = flops.greedy_round_work(49_000, 512, 1, weighted=True)
    assert bw - b == 4 * 49_000


def test_least_time_takes_the_binding_roof():
    f, b = flops.greedy_round_work(49_000, 512, 1)
    t = flops.least_time_s(f, b, 197e12, 819e9)
    assert t == b / 819e9                       # memory-bound at R = 1
    f, b = flops.greedy_round_work(50_000, 512, 8192)
    assert flops.least_time_s(f, b, 197e12, 819e9) == f / 197e12


def test_al_round_counts_fold_picks_and_probs():
    got = flops.al_round_flops(49_000, 50_000, 512, 10, 1000)
    assert got == (2 * 49_000 * 1000 * 512 + 1000 * 2 * 49_000 * 512
                   + 2 * 50_000 * 512 * 10)
