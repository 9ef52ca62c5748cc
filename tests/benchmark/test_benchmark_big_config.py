"""``twitter_wpr_big`` is ``twitter_wpr`` at another scale and nothing
else: every shape of the deployment (R-MAT parameters, events per id,
time span, algorithm and its iterations, windows, hop, tail, guarantees,
limits of ``correct``) is the committed file's, key for key."""

from benchmark import run

#: what may differ, as dotted paths: the size and the prose about it
MAY_DIFFER = {"name", "source", "graph.scale", "graph.note", "reduced",
              "deployment", "correct.readings"}


def _flat(doc, prefix=""):
    out = {}
    for k, v in doc.items():
        path = f"{prefix}{k}"
        if isinstance(v, dict) and path not in MAY_DIFFER:
            out.update(_flat(v, path + "."))
        else:
            out[path] = v
    return out


def test_the_big_configuration_differs_in_scale_and_prose_only():
    small = _flat(run.load_json(run.HERE, "configs", "twitter_wpr.json"))
    big = _flat(run.load_json(run.HERE, "configs", "twitter_wpr_big.json"))
    assert set(small) == set(big)
    differ = {k for k in small if small[k] != big[k]}
    assert differ == MAY_DIFFER
    assert (small["graph.scale"], big["graph.scale"]) == (17, 18)
    # the cuts are the same two, and each says from what to what
    assert sorted(big["reduced"]) == ["events", "ids"]
    assert "8,388,608" in big["reduced"]["events"]
    assert "262,144" in big["reduced"]["ids"]
