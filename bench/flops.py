"""Useful training operations of the paper's CNN, counted from shapes.

Forward multiply-adds per sample: each VALID 5x5 convolution costs
out_h * out_w * (k * k * c_in) * c_out, each dense layer in * out; the
max-pools and ReLUs are not counted. Training counts forward plus
backward as three forward passes of two operations per multiply-add.
Only valid samples count, not the padding that fills a device's rows up
to the largest D_n.
"""
from __future__ import annotations


def forward_macs(model):
    """Multiply-adds of one sample's forward pass; ``model`` is a
    configuration file's "model" group."""
    h, w = model["image_hw"]
    k, c = model["kernel"], model["channels"]
    macs = 0
    for c_out in (model["conv1"], model["conv2"]):
        h, w = h - k + 1, w - k + 1
        macs += h * w * k * k * c * c_out
        h, w, c = h // 2, w // 2, c_out
    flat = h * w * c
    return macs + flat * model["hidden"] + model["hidden"] * model["n_classes"]


def train_flops(model, samples):
    """Operations of forward and backward over ``samples`` sample-steps."""
    return 3 * 2 * forward_macs(model) * samples
