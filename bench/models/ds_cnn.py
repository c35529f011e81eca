"""MLPerf Tiny keyword spotting (Speech Commands): DS-CNN.

49x10 MFCC frames, one channel.  A 5x5/2 conv to 64 channels, four
depthwise-separable blocks (3x3 depthwise + bias + ReLU, then 1x1 conv +
bias + ReLU, 64 channels), global average pooling, a dense layer to the
12 classes and a softmax.  Batch norm is folded away, as in the program's
graph.  The source's first conv is 10x4/2; the served graph's is 5x5/2,
because the program builds square kernels only, and this reference
follows what is served (the configuration's ``ds_cnn_conv1_kernel``).
"""

INPUT = ("x", (49, 10, 1))
OUTPUT = "prob:out"


def params():
    out = {"conv0_w": (5, 5, 1, 64), "conv0_b": (64,)}
    for i in range(4):
        out[f"dw{i}_w"], out[f"dw{i}_b"] = (3, 3, 64, 1), (64,)
        out[f"pw{i}_w"], out[f"pw{i}_b"] = (1, 1, 64, 64), (64,)
    out["fc_w"], out["fc_b"] = (64, 12), (12,)
    return out


def forward(p, x, nn):
    h = nn.relu(nn.conv(x, p["conv0_w"], 2) + p["conv0_b"])
    for i in range(4):
        h = nn.relu(nn.dwconv(h, p[f"dw{i}_w"], 1) + p[f"dw{i}_b"])
        h = nn.relu(nn.conv(h, p[f"pw{i}_w"], 1) + p[f"pw{i}_b"])
    h = nn.mean_hw(h)
    return nn.softmax(nn.dense(h, p["fc_w"]) + p["fc_b"])
