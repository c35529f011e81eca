"""The ssd's operation and byte counts against the program's own count
and against a count by hand."""

import op_counts

# granite-4.0-h-micro's mixer: 64 heads of 64 channels, state 128,
# chunks of 256, over a 2048-token prompt
PUBLISHED = dict(heads=64, head_dim=64, state=128)


def test_published_mixer():
    macs = op_counts.ssd_macs(2048, **PUBLISHED, chunk=256)
    # 8 chunks of (256*256*128 + 64*256*256*64 + 2*64*256*64*128)
    assert macs == 8 * 2_129_920 * 256 == 4_362_076_160
    assert op_counts.ssd_flops(2048, **PUBLISHED) == 2 * macs
    # x and y (2048 x 4096), dt (2048 x 64), B and C (2048 x 128 each)
    # and three (64,) parameters, in float32
    assert op_counts.ssd_bytes(2048, **PUBLISHED) == 4 * (
        2 * 2048 * 4096 + 2048 * 64 + 2 * 2048 * 128 + 3 * 64)


def test_short_and_part_chunks():
    # under a chunk: one chunk of the sequence's own length
    assert op_counts.ssd_macs(10, 2, 4, 3, chunk=16) == (
        10 * 10 * 3 + 2 * 10 * 10 * 4 + 2 * 2 * 10 * 4 * 3)
    # a part chunk is padded to a whole one
    assert op_counts.ssd_macs(17, 2, 4, 3, chunk=16) == \
        2 * op_counts.ssd_macs(16, 2, 4, 3, chunk=16)


def test_matches_the_program():
    from repro.core.ir import op_macs
    from repro.models.granite import granite_h_micro_stage4
    g = granite_h_micro_stage4()
    ssd = [op for op in g.topo_ops() if op.op_type == "ssd"]
    assert len(ssd) == 9
    for op in ssd:
        assert op_macs(g, op) == op_counts.ssd_macs(2048, **PUBLISHED)
