"""GoogLeNet (Inception v1) as published: Szegedy et al. 2015,
arXiv:1409.4842, Table 1, at inference: no auxiliary classifiers, dropout a
no-op, and the 7x7 average pool over the 7x7 map is a global average.  Max
pools are the original Caffe model's, in ceil mode.  LRN is left out, as the
served graph leaves it out (listed in the configuration's ``reduced``).
Layer names are the ones the served graph gives its weights."""


def layers(cfg: dict) -> list:
    out = [{"op": "input", "name": "data"}]

    def conv(name, src, oc, k, s=1):
        out.append({"op": "conv", "name": name, "in": src, "k": k, "s": s,
                    "oc": oc, "relu": True})
        return name

    def pool(name, src, k=3, s=2, pad=0):
        out.append({"op": "maxpool", "name": name, "in": src, "k": k, "s": s,
                    "pad": pad})
        return name

    last = pool("pool1", conv("conv1", "data", 64, 7, 2))
    last = pool("pool2", conv("conv2", conv("conv2r", last, 64, 1), 192, 3))
    for mod in cfg["inception"]:
        if mod in ("4a", "5a"):
            last = pool(f"pool{3 if mod == '4a' else 4}", last)
        c1, r3, c3, r5, c5, pp = cfg["inception"][mod]
        n = f"inc{mod}"
        b1 = conv(f"{n}/1x1", last, c1, 1)
        b2 = conv(f"{n}/3x3", conv(f"{n}/3x3r", last, r3, 1), c3, 3)
        b3 = conv(f"{n}/5x5", conv(f"{n}/5x5r", last, r5, 1), c5, 5)
        b4 = conv(f"{n}/poolp", pool(f"{n}/pool", last, 3, 1, 1), pp, 1)
        out.append({"op": "concat", "name": f"{n}/out",
                    "ins": [b1, b2, b3, b4]})
        last = f"{n}/out"
    out.append({"op": "gap", "name": "gap", "in": last})
    out.append({"op": "fc", "name": "fc", "in": "gap",
                "oc": cfg["num_classes"]})
    if cfg["softmax"]:
        out.append({"op": "softmax", "name": "prob", "in": "fc"})
    return out
