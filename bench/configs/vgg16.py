"""VGG-16 as published: Simonyan & Zisserman 2014, arXiv:1409.1556, Table 1,
configuration D: 3x3/1 convolutions with padding 1 and ReLU in five groups,
a 2x2/2 max pool after each group, two fc layers of 4096 with ReLU, the
classifier fc and softmax.  At inference dropout is the identity.  The fc
layers read the last pool's map flattened in NHWC order, as the served graph
does.  Layer names are the ones the served graph gives its nodes."""


def layers(cfg: dict) -> list:
    out = [{"op": "input", "name": "data"}]
    last, ci = "data", 0
    for group in cfg["conv_widths"]:
        for oc in group:
            ci += 1
            out.append({"op": "conv", "name": f"conv{ci}", "in": last,
                        "k": 3, "s": 1, "oc": oc, "relu": True})
            last = f"conv{ci}"
        out.append({"op": "maxpool", "name": f"pool{ci}", "in": last,
                    "k": 2, "s": 2})
        last = f"pool{ci}"
    for i, oc in enumerate(cfg["fc_widths"] + [cfg["num_classes"]]):
        name = f"fc{6 + i}"
        out.append({"op": "fc", "name": name, "in": last, "oc": oc,
                    "relu": i < len(cfg["fc_widths"])})
        last = name
    if cfg["softmax"]:
        out.append({"op": "softmax", "name": "prob", "in": last})
    return out
