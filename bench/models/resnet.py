"""MLPerf Tiny image classification (CIFAR-10): ResNet-8.

32x32x3 images.  A 3x3 conv to 16 channels, then three residual stacks of
16, 32 and 64 channels; the second and third stride by 2 and take a 1x1
strided projection (no bias) as their shortcut.  In each block the first
3x3 conv has bias and ReLU, the second has neither, and the ReLU follows
the residual add.  Global average pooling, a dense layer to 10 classes
and a softmax.  Batch norm is folded away, as in the program's graph.
"""

INPUT = ("x", (32, 32, 3))
OUTPUT = "prob:out"

_BLOCKS = (("b1", 16, 16, 1), ("b2", 16, 32, 2), ("b3", 32, 64, 2))


def params():
    out = {"conv0_w": (3, 3, 3, 16), "conv0_b": (16,)}
    for name, cin, cout, stride in _BLOCKS:
        out[f"{name}_c1_w"], out[f"{name}_c1_b"] = (3, 3, cin, cout), (cout,)
        out[f"{name}_c2_w"] = (3, 3, cout, cout)
        if stride != 1 or cin != cout:
            out[f"{name}_sc_w"] = (1, 1, cin, cout)
    out["fc_w"], out["fc_b"] = (64, 10), (10,)
    return out


def forward(p, x, nn):
    h = nn.relu(nn.conv(x, p["conv0_w"], 1) + p["conv0_b"])
    for name, cin, cout, stride in _BLOCKS:
        y = nn.relu(nn.conv(h, p[f"{name}_c1_w"], stride) + p[f"{name}_c1_b"])
        y = nn.conv(y, p[f"{name}_c2_w"], 1)
        sc = (nn.conv(h, p[f"{name}_sc_w"], stride)
              if stride != 1 or cin != cout else h)
        h = nn.relu(y + sc)
    h = nn.mean_hw(h)
    return nn.softmax(nn.dense(h, p["fc_w"]) + p["fc_b"])
