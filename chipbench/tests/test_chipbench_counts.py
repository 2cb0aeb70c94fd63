"""The benchmark's counts against hand counts at tiny sizes."""
import pytest

from chipbench import counts
from chipbench.reference import whisper_base, xlstm_125m

XL = {"d_model": 8, "num_heads": 2, "num_layers": 2, "vocab_size": 10,
      "ssm": {"chunk": 4, "slstm_every": 2}}
WH = {"d_model": 4, "d_ff": 8, "num_heads": 2, "head_dim": 2,
      "vocab_size": 10, "encoder_layers": 1, "num_layers": 1,
      "encoder_downsample": 2, "decoder_len_cap": 448}


def test_xlstm_flops_by_hand():
    # layer 0 is sLSTM, layer 1 mLSTM; d 8, 2 heads of 4, chunk 4, seq 8
    mlstm = (2 * 8 * 16 + 3 * 2 * 64 + 2 * 8 * 4 + 2 * 64   # projections
             + 2 * 2 * 4 * 8                               # intra-chunk
             + 2 * 2 * 4 * 8                               # chunk state
             + 2 * 2 * 8)                                  # normaliser
    slstm = 2 * 8 * 32 + 2 * 2 * 4 * 16 + 2 * 64           # incl. recurrence
    head = 2 * 8 * 10
    assert xlstm_125m.forward_flops_per_token(XL, 8) == mlstm + slstm + head
    traffic = {"global_batch": 3, "seq_len": 8}
    assert xlstm_125m.train_flops(XL, traffic) == 3 * 24 * (mlstm + slstm
                                                           + head)


def test_xlstm_flops_chunk_caps_at_sequence():
    assert (xlstm_125m.forward_flops_per_token(XL, 2)
            < xlstm_125m.forward_flops_per_token(XL, 8))


def test_whisper_flops_by_hand():
    # seq 32 frames -> 16 encoder frames, 16 decoder tokens (the floor)
    te, td = whisper_base.lengths(WH, 32)
    assert (te, td) == (16, 16)
    enc = (2 * 16 * 4 * 4 * 4          # q, k, v, o on encoder frames
           + 2 * 2 * 16 * 16 * 4       # scores and weights . v
           + 2 * 2 * 16 * 4 * 8)       # MLP
    dec = (2 * 16 * 4 * 4 * 4          # self q, k, v, o
           + 2 * 2 * 16 * 16 * 4       # self scores, weights . v
           + 2 * 2 * 16 * 4 * 4        # cross q, o on decoder tokens
           + 2 * 2 * 16 * 4 * 4        # cross k, v on encoder frames
           + 2 * 2 * 16 * 16 * 4       # cross scores, weights . v
           + 2 * 2 * 16 * 4 * 8)       # MLP
    head = 2 * 16 * 4 * 10
    assert whisper_base.forward_flops(WH, 32) == enc + dec + head


def test_whisper_encoder_weights_charged_to_encoder_frames_only():
    # more frames with the same decoder length: only encoder-side terms
    # (encoder blocks, cross keys/values, cross scores) grow
    a = dict(WH, decoder_len_cap=16)
    base = whisper_base.forward_flops(a, 256)
    wider = whisper_base.forward_flops(a, 512)
    te0, td = whisper_base.lengths(a, 256)
    te1, _ = whisper_base.lengths(a, 512)
    assert td == 16
    d, f, att = 4, 8, 4
    per_frame_linear = (2 * d * att * 4 + 2 * 2 * d * f   # encoder block
                        + 2 * 2 * d * att)                # cross k, v
    quad = 2 * 2 * att
    expect = ((te1 - te0) * per_frame_linear
              + quad * (te1 ** 2 - te0 ** 2)
              + 2 * 2 * td * att * (te1 - te0))
    assert wider - base == expect


def test_codec_bytes():
    # 1000 bf16 elements at 1 bit, two neighbours
    assert counts.encode_bytes(1000, 2, 1) == 2000 + 125
    assert counts.decode_reduce_bytes(1000, 2, 1, 2) == 4000 + 3 * 125
    assert counts.decode_reduce_bytes(1000, 2, 8, 2) == 4000 + 3 * 1000
    assert counts.flat_elems([(2, 3), (5,), (4, 1, 2)]) == 6 + 5 + 8


def test_peaks_by_device_kind():
    p = counts.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks("cpu")
