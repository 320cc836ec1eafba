"""``dispatch_rows_over_routed``: the buffer rows the routed layer's
dispatch wrote (row counter ``dispatch_rows_moved``) over the pairs routed
here (``expert_pairs_here``), read by ``readers/row_counter_ratio.py`` in
``mellum2_n10_median`` alone; a program that stamps no such counter (one
whose dispatch is XLA's gather) gives nothing, not an error."""

from pb.manifest import CHECKOUT, Manifest, reader

METRIC = "dispatch_rows_over_routed"


def test_the_metric_is_declared_for_the_code_model_cell_alone():
    m = Manifest(CHECKOUT)
    entry = m.per_layer[METRIC]
    assert entry["workloads"] == ["mellum2_n10_median"]
    assert entry["moves"] == "rounds_per_s"
    assert entry["better"] == "lower"
    spec = m.metric_file(METRIC)
    assert spec["reader"] == "row_counter_ratio"
    assert (spec["counter"], spec["over_counter"]) == (
        "dispatch_rows_moved", "expert_pairs_here")
    # the same layer as the grouped product's row ratio, letter for letter
    assert entry["layer"] == m.per_layer["expert_rows_over_routed"]["layer"]


def test_the_reader_divides_the_traced_rounds_rows():
    read = reader("row_counter_ratio")
    spec = Manifest(CHECKOUT).metric_file(METRIC)
    # 32 (lane, layer)s of 8192 held pairs, each written as 32 tiles of 256
    rows = [{"dispatch_rows_moved": 262144, "expert_pairs_here": 262144},
            {"dispatch_rows_moved": 270336, "expert_pairs_here": 262144},
            {"dispatch_rows_moved": 2097152, "expert_pairs_here": 262144}]
    assert read({"rows": rows, "traced_rounds": 2}, spec) == (
        262144 + 270336) / (2 * 262144)
    # XLA's gathers write the whole buffer, tokens x top-k: 8x here
    assert read({"rows": rows[2:]}, spec) == 8.0


def test_a_program_without_the_counter_reports_nothing():
    read = reader("row_counter_ratio")
    spec = Manifest(CHECKOUT).metric_file(METRIC)
    parent = [{"expert_pairs_here": 262144, "expert_rows_computed": 286361}]
    assert read({"rows": parent}, spec) is None
    assert read({"rows": [{"dispatch_rows_moved": 0,
                           "expert_pairs_here": 0}]}, spec) is None
