"""A configuration is cut only as its family allows: the depth, the
routed experts held and the vocabulary, each beside its published value
and above the model-configs guide's floors; a width, a missing or
non-larger published value and a broken floor are refused by
``harness.resolve``, naming the key."""
import json
import os
import re

import pytest

import harness
import tiny
from tiny import CELL

# gqa_decoder, whose configurations may also hold fewer routed experts
EXPERTS_FAMILY = '''"""gqa_decoder with a routed-experts cut."""
import harness

_G = harness.load_family({"family": "gqa_decoder"})
CUTS = dict(_G.CUTS, experts="n_routed_experts")
min_layers = _G.min_layers
'''
# a family that would cut a width
WIDTH_FAMILY = '''CUTS = {"depth": "num_hidden_layers",
        "width": "hidden_size"}
'''
DEPLOYMENT = "eight chips share each layer"


def cut(family="gqa_decoder", **held):
    """The tiny configuration holding ``held`` ({key: (held, published)}),
    each key listed in ``reduced``."""
    return tiny.config(
        family=family, deployment=DEPLOYMENT, reduced=list(held),
        published={k: p for k, (_, p) in held.items()},
        **{k: h for k, (h, _) in held.items()})


ACCEPTED = {
    "depth": cut(num_hidden_layers=(4, 6)),
    "vocab_an_eighth": cut(vocab_size=(200, 1600)),
    "experts": cut("experts_family", n_routed_experts=(8, 64)),
    "all_three": cut("experts_family", num_hidden_layers=(4, 27),
                     n_routed_experts=(8, 64), vocab_size=(200, 1600)),
}


def _without(c, key):
    c = dict(c)
    del c[key]
    return c


REFUSED = {
    "hidden_size": (cut(hidden_size=(64, 128)), "hidden_size",
                    "widths are never cut"),
    "head_dim": (cut(head_dim=(16, 32)), "head_dim",
                 "widths are never cut"),
    "intermediate_size": (cut(intermediate_size=(128, 256)),
                          "intermediate_size", "widths are never cut"),
    "a_family_that_cuts_a_width": (cut("width_family",
                                       hidden_size=(64, 128)),
                                   "hidden_size", "widths are never cut"),
    "experts_not_in_the_family": (cut(n_routed_experts=(8, 64)),
                                  "n_routed_experts", "does not name"),
    "no_published": (dict(cut(num_hidden_layers=(4, 6)), published={}),
                     "num_hidden_layers", "no published value"),
    "published_not_above": (cut(num_hidden_layers=(4, 4)),
                            "num_hidden_layers", "below the published"),
    "four_experts": (cut("experts_family", n_routed_experts=(4, 64)),
                     "n_routed_experts", "at least 8"),
    "vocab_under_an_eighth": (cut(vocab_size=(200, 1601)), "vocab_size",
                              "at least 201"),
    "too_few_layers": (cut(num_hidden_layers=(3, 6)), "num_hidden_layers",
                       "at least 4"),
    "no_deployment": (_without(cut(num_hidden_layers=(4, 6)),
                               "deployment"), "deployment", "states no"),
}


def _resolve(tmp_path, config):
    root = tiny.checkout(tmp_path, config,
                         {"experts_family": EXPERTS_FAMILY,
                          "width_family": WIDTH_FAMILY})
    return harness.resolve(CELL, root)


@pytest.mark.parametrize("case", sorted(ACCEPTED))
def test_a_cut_the_family_allows_is_taken(tmp_path, case):
    c = ACCEPTED[case]
    cell = _resolve(tmp_path, c)
    assert cell.config == c
    assert cell.family.min_layers(c) == 4


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_a_cut_past_the_rules_is_refused(tmp_path, case):
    c, key, why = REFUSED[case]
    with pytest.raises(ValueError, match=re.escape(key)) as e:
        _resolve(tmp_path, c)
    assert why in str(e.value)


def test_benchmark_and_file_state_the_same_cuts(tmp_path):
    root = tiny.checkout(tmp_path, ACCEPTED["depth"])
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"][-1]["reduced"] = []
    with open(path, "w") as f:
        json.dump(bench, f)
    with pytest.raises(ValueError, match="num_hidden_layers"):
        harness.resolve(CELL, root)

