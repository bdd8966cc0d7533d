"""``BENCHMARK.json``'s lists stay appendable.  A later PR adds its cells,
configurations and per-layer metrics by new files and entries at the end of
each list, and may not edit a file the benchmark already has.  So no test
here may read an entry by its place from the end (``["per_layer"][-1]``,
``[:-1]``, ``cell.per_layer()[-6:]``): the next entry appended would move
it.  Held here: no module of this directory reads such a list by a
negative index, the scan finds each form of such a pin, and the loader
takes a cell whose configuration, traffic, limits and one appended
per-layer entry are files and entries alone."""
import ast
import copy
import glob
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, ROOT)

from benchmark import cells  # noqa: E402
from benchmark.readers import scopes  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
LISTS = {"per_layer", "workloads", "configs", "end_to_end"}


def _names_a_list(node):
    """``x["per_layer"]`` or ``cell.per_layer()`` (and the like)."""
    if isinstance(node, ast.Subscript):
        key = node.slice
        return isinstance(key, ast.Constant) and key.value in LISTS
    return isinstance(node, ast.Call) \
        and isinstance(node.func, ast.Attribute) and node.func.attr in LISTS


def _negative(node):
    """An index or slice that counts from the end or is worked out by a
    subtraction: ``-1``, ``:-1``, ``-6:``, ``len(x) - 1``."""
    return any(isinstance(n, ast.UnaryOp) and isinstance(n.op, ast.USub)
               or isinstance(n, ast.BinOp) and isinstance(n.op, ast.Sub)
               for n in ast.walk(node))


def pins(source):
    """Line numbers at which ``source`` reads one of the lists by place
    from the end."""
    return sorted(n.lineno for n in ast.walk(ast.parse(source))
                  if isinstance(n, ast.Subscript) and _names_a_list(n.value)
                  and _negative(n.slice))


@pytest.mark.parametrize("source", [
    'e = BENCH["per_layer"][-1]',
    "e = BENCH['per_layer'][:-1]",
    'e = bench["workloads"] [ -2 ]',
    'e = BENCH["configs"][len(BENCH["configs"]) - 1]',
    'e = cell.bench["per_layer"][-6:]',
    "e = c.per_layer()[-len(ENTRIES):]",
    'e = BENCH["end_to_end"][::-1]'])
def test_the_scan_finds_a_pin_put_back(source):
    assert pins(source) == [1]


def test_the_scan_leaves_reads_by_name_and_from_the_front():
    assert pins('e = BENCH["per_layer"][0]\n'
                'f = [m for m in BENCH["per_layer"] if m["name"] == "x"]\n'
                'g = BENCH["workloads"][1:]\n'
                'h = names[names.index("x") - 1]\n'
                'i = "BENCH[\\"per_layer\\"][-1]"\n') == []


def test_no_test_module_reads_a_list_by_place_from_the_end():
    found = {}
    for path in sorted(glob.glob(os.path.join(HERE, "*.py"))):
        with open(path) as f:
            lines = pins(f.read())
        if lines:
            found[os.path.basename(path)] = lines
    assert found == {}


# ------------------------------------------------ what the next PR appends
NEW_CELL = "made-steps-t4096"
NEW_METRIC = "made.rotary_ms"


def _appended(root):
    """A copy of the benchmark under ``root`` with one configuration, one
    traffic mix, one cell and one per-layer metric added by files and by
    entries at the end of each list; the metric's reader is an existing
    function named by a file that is data alone."""
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = os.path.join(root, "benchmark")
    cfg = json.load(open(os.path.join(b, "configs",
                                      "kimi-linear-48b-a3b.json")))
    cfg["made"] = True
    json.dump(cfg, open(os.path.join(b, "configs", "made.json"), "w"))
    tr = json.load(open(os.path.join(b, "traffic", "steps-b1.json")))
    json.dump(dict(tr, made=True),
              open(os.path.join(b, "traffic", "made-b1.json"), "w"))
    json.dump({"mom8_med": 0.001},
              open(os.path.join(b, "limits", NEW_CELL + ".json"), "w"))
    json.dump({"module": "scopes", "function": "scope_ms",
               "args": {"scopes": ["rotary"]}},
              open(os.path.join(b, "metrics", NEW_METRIC + ".json"), "w"))
    bench = copy.deepcopy(BENCH)
    bench["configs"].append({"name": "made", "source": "made for the test",
                             "file": "benchmark/configs/made.json",
                             "reduced": [], "why": "made"})
    bench["workloads"].append({"name": NEW_CELL, "config": "made",
                               "traffic": "made-b1", "chips": 1,
                               "why": "made"})
    bench["per_layer"].append({"name": NEW_METRIC, "unit": "ms",
                               "better": "lower", "source": "device_trace",
                               "layer": "latent attention",
                               "moves": "train_items_per_s",
                               "workloads": [NEW_CELL]})
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"),
              indent=1)
    return root


def test_an_appended_entry_is_taken_for_its_cell_alone(tmp_path):
    root = _appended(str(tmp_path))
    new = cells.Cell(NEW_CELL, root=root)
    assert new.config["made"] and new.traffic["made"]
    assert new.limits == {"mom8_med": 0.001}
    names = [m["name"] for m in new.per_layer()]
    assert NEW_METRIC in names and "setup.rest_s" in names
    assert new.reader(NEW_METRIC) == (scopes.scope_ms,
                                      {"scopes": ["rotary"]})
    for w in BENCH["workloads"]:
        # every cell there was reads what it read before the append
        assert cells.Cell(w["name"], root=root).per_layer() \
            == cells.Cell(w["name"]).per_layer()
