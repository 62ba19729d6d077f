"""ResNet-50 as published: He et al. 2015, arXiv:1512.03385, Table 1 (the
50-layer column), with bottleneck blocks (Fig. 5 right) and projection
shortcuts where the shape changes (option B).  Batch norm is at its inference
defaults, and pool1 is the original Caffe model's 3x3/2 max pool in ceil mode.
Layer names are the ones the served graph gives its weights."""


def layers(cfg: dict) -> list:
    out = [{"op": "input", "name": "data"}]

    def conv(name, src, oc, k, s=1, relu=True):
        out.append({"op": "conv", "name": name, "in": src, "k": k, "s": s,
                    "oc": oc, "bn": True, "relu": relu})
        return name

    last = conv("conv1", "data", 64, 7, 2)
    out.append({"op": "maxpool", "name": "pool1", "in": last, "k": 3, "s": 2})
    last = "pool1"
    for si, (nb, mid, wide) in enumerate(zip(cfg["blocks"], cfg["mid_widths"],
                                            cfg["out_widths"])):
        for bi in range(nb):
            s = 2 if (bi == 0 and si > 0) else 1
            b = f"s{si}b{bi}"
            a = conv(f"{b}/c1", last, mid, 1)
            a = conv(f"{b}/c2", a, mid, 3, s)
            a = conv(f"{b}/c3", a, wide, 1, relu=False)
            sc = conv(f"{b}/sc", last, wide, 1, s, relu=False) if bi == 0 \
                else last
            out.append({"op": "add", "name": f"{b}/add", "ins": [a, sc],
                        "relu": True})
            last = f"{b}/add"
    out.append({"op": "gap", "name": "gap", "in": last})
    out.append({"op": "fc", "name": "fc", "in": "gap",
                "oc": cfg["num_classes"]})
    if cfg["softmax"]:
        out.append({"op": "softmax", "name": "prob", "in": "fc"})
    return out
