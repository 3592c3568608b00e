"""Analytic operation counts and the chips' peaks.

Counts are of the algorithm: the multiply-adds of matrix products and
convolutions (2 operations each), at the shapes a call runs; elementwise
work, recomputation and padding are not counted.
"""

from __future__ import annotations


# Per chip. Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s in
# bf16, 16 GB of HBM at 819 GB/s).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    """The peaks of one chip; an unknown chip is an error, not a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it "
                       "to benchmarks/chip/flops.py with its source")
    return PEAKS[device_kind]


# ---------------------------------------------------------------------------
# IMPALA deep ResNet


def _conv3x3(h, w, cin, cout):
    """A SAME 3x3 convolution's products with real (unpadded) inputs: a
    row of n outputs meets 3n - 2 of them."""
    return 2 * (3 * h - 2) * (3 * w - 2) * cin * cout


def impala_conv_flops(obs_shape, channels):
    """Forward operations of each convolution, per frame, in order."""
    h, w, cin = obs_shape
    out = []
    for ch in channels:
        out.append(_conv3x3(h, w, cin, ch))
        h, w = -(-h // 2), -(-w // 2)
        out += [_conv3x3(h, w, ch, ch)] * 4
        cin = ch
    return out, (h, w)


def impala_forward_flops(obs_shape, channels, fc, num_actions):
    """Forward operations per frame: convolutions and the dense layers."""
    convs, (h, w) = impala_conv_flops(obs_shape, channels)
    dense = 2 * (h * w * channels[-1] * fc + fc * num_actions + fc)
    return sum(convs) + dense


def impala_step_flops(obs_shape, channels, fc, num_actions, t, b):
    """One learner step with its actors' unroll: the actors' forward on
    T*B frames, the learner's forward on (T+1)*B frames and its backward
    (weight gradients for every layer, input gradients for every layer but
    the first)."""
    fwd = impala_forward_flops(obs_shape, channels, fc, num_actions)
    first = impala_conv_flops(obs_shape, channels)[0][0]
    return t * b * fwd + (t + 1) * b * (3 * fwd - first)
