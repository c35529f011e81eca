"""MLPerf Tiny visual wake words: MobileNetV1, width multiplier 0.25.

96x96x3 images.  A 3x3/2 conv to 8 channels, then 13 depthwise-separable
blocks (3x3 depthwise + bias + ReLU, then 1x1 conv + bias + ReLU) with the
published channel and stride schedule, global average pooling, a dense
layer to the 2 classes and a softmax.  Batch norm is folded away and
ReLU6 is a plain ReLU, as in the program's graph.
"""

INPUT = ("x", (96, 96, 3))
OUTPUT = "prob:out"

_BLOCKS = ((8, 16, 1), (16, 32, 2), (32, 32, 1), (32, 64, 2), (64, 64, 1),
           (64, 128, 2), (128, 128, 1), (128, 128, 1), (128, 128, 1),
           (128, 128, 1), (128, 128, 1), (128, 256, 2), (256, 256, 1))


def params():
    out = {"conv0_w": (3, 3, 3, 8), "conv0_b": (8,)}
    for i, (cin, cout, _) in enumerate(_BLOCKS):
        out[f"dw{i}_w"], out[f"dw{i}_b"] = (3, 3, cin, 1), (cin,)
        out[f"pw{i}_w"], out[f"pw{i}_b"] = (1, 1, cin, cout), (cout,)
    out["fc_w"], out["fc_b"] = (256, 2), (2,)
    return out


def forward(p, x, nn):
    h = nn.relu(nn.conv(x, p["conv0_w"], 2) + p["conv0_b"])
    for i, (_, _, stride) in enumerate(_BLOCKS):
        h = nn.relu(nn.dwconv(h, p[f"dw{i}_w"], stride) + p[f"dw{i}_b"])
        h = nn.relu(nn.conv(h, p[f"pw{i}_w"], 1) + p[f"pw{i}_b"])
    h = nn.mean_hw(h)
    return nn.softmax(nn.dense(h, p["fc_w"]) + p["fc_b"])
