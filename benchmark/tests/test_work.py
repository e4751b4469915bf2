"""The work functions against hand counts."""
from benchmark.trace import work

PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}

MODEL = """tree
version=v4
num_class=1

Tree=0
num_leaves=4
num_cat=0
split_feature=0 1 2
left_child=1 -1 -2
right_child=2 -3 -4
leaf_count=10 25 30 35
internal_count=100 40 60

Tree=1
num_leaves=1
leaf_value=0

Tree=2
num_leaves=2
left_child=-1
right_child=-2
leaf_count=7 93
internal_count=100

end of trees
"""


def test_histogram_pass_hand_count():
    # 1000 rows x 10 features: 10 bin bytes + 8 gh bytes a row, 2 adds per
    # row and feature
    assert work.histogram_pass(1000, 10) == {"bytes": 18000, "ops": 20000}


def test_gradient_and_score_pass_hand_count():
    assert work.gradient_pass(100) == {"bytes": 1600, "ops": 800}
    assert work.score_pass(100) == {"bytes": 800, "ops": 100}


def test_boosting_iteration_adds_its_parts():
    w = work.boosting_iteration(rows=100, features=10, hist_rows=250)
    assert w["bytes"] == 250 * 18 + 1600 + 800
    assert w["ops"] == 2 * 250 * 10 + 800 + 100


def test_least_seconds_names_its_bound():
    s, bound = work.least_seconds({"bytes": 819e9, "ops": 1e9}, PEAKS)
    assert bound == "bytes" and abs(s - 1.0) < 1e-12
    s, bound = work.least_seconds({"bytes": 1, "ops": 197e12}, PEAKS)
    assert bound == "ops" and abs(s - 1.0) < 1e-12


def test_tree_counts_from_model_text():
    # tree 0: root 100 -> (40, 60); node 1 (40) -> leaves 10, 30;
    # node 2 (60) -> leaves 25, 35. Smaller children: 40, 10, 25.
    # tree 1 is a stump and grew no histogram; tree 2: 7 of 100.
    counts = work.tree_counts_from_model_text(MODEL)
    assert counts == [(100, [40, 10, 25]), (100, [7])]
    assert work.histogram_rows(counts[0]) == 175
