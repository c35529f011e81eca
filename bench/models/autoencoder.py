"""MLPerf Tiny anomaly detection (ToyADMOS): the dense autoencoder.

A 640-wide frame of log-mel features goes through four 128-wide layers,
an 8-wide bottleneck, four more 128-wide layers and a 640-wide linear
reconstruction; every hidden layer is dense + bias + ReLU.  Batch norm of
the MLPerf reference is folded away, as in the program's graph.
"""

INPUT = ("x", (640,))
OUTPUT = "fc_out_bias:out"

_WIDTHS = (128, 128, 128, 128, 8, 128, 128, 128, 128)


def params():
    """Parameter name -> shape, as the served graph names them."""
    out, cin = {}, 640
    for i, w in enumerate(_WIDTHS):
        out[f"fc{i}_w"], out[f"fc{i}_b"] = (cin, w), (w,)
        cin = w
    out["fc_out_w"], out["fc_out_b"] = (128, 640), (640,)
    return out


def forward(p, x, nn):
    h = x
    for i in range(len(_WIDTHS)):
        h = nn.relu(nn.dense(h, p[f"fc{i}_w"]) + p[f"fc{i}_b"])
    return nn.dense(h, p["fc_out_w"]) + p["fc_out_b"]
