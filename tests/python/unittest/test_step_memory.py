"""tools/step_memory.py's reading of XLA's buffer assignment: the scratch
allocation's live set at its fullest (PERF.md 7, PR 38), on made dumps."""
import importlib.util
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[3]


def _tool():
    spec = importlib.util.spec_from_file_location(
        "step_memory", ROOT / "tools" / "step_memory.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _dump(values, ranges, temp_size=1000):
    """A buffer-assignment text in the compiler's form: one uncoloured
    scratch allocation holding ``values`` (name, size, offset), a coloured
    one and a parameter that must not count, then the live ranges."""
    lines = ["allocation 0: size 64, parameter 0, shape |f32[16]| at "
             "ShapeIndex {}, maybe-live-out:",
             " value: <1 p.1 @0> (size=64,offset=0): f32[16]{0}",
             "allocation 1: size %d, preallocated-temp:" % temp_size]
    for i, (name, size, offset) in enumerate(values):
        lines.append(" value: <%d %s @0> (size=%d,offset=%d): f32[%d]{0:"
                     "T(8,128)}" % (10 + i, name, size, offset, size // 4))
    lines += ["allocation 2: size 512, color 1, preallocated-temp:",
              " value: <99 c.1 @0> (size=512,offset=0): f32[128]{0}",
              "", "HloLiveRange (max 40):", "  InstructionSequence:",
              "    0:p.1", "  BufferLiveRange:", "    p.1{}:0-40",
              "    c.1{}:0-40"]
    lines += ["    %s:%d-%d" % (n if "{" in n else n + "{}", a, b)
              for n, (a, b) in ranges.items()]
    lines += ["  Live ranges at 3 (peak):", "    p.1{}: 64 bytes"]
    return "\n".join(lines) + "\n"


@pytest.mark.parametrize("values,ranges,want", [
    # three that overlap at 5-6: the fullest point holds a, b and c
    ([("a", 400, 0), ("b", 200, 400), ("c", 100, 600), ("d", 300, 0)],
     {"a": (2, 6), "b": (5, 9), "c": (4, 8), "d": (7, 20)},
     (700, 700, 5, ["a{}", "b{}", "c{}"])),
    # one after another in the same bytes: the largest alone
    ([("a", 400, 0), ("b", 300, 0), ("t{1}", 200, 400)],
     {"a": (0, 3), "b": (4, 9), "t{1}": (10, 12)},
     (600, 400, 0, ["a{}"])),
])
def test_the_live_set_at_the_scratchs_fullest(values, ranges, want):
    got = _tool().live_at_peak(_dump(values, ranges))
    extent, peak, at, names = want
    assert (got["extent"], got["live_peak"], got["at"]) == (extent, peak, at)
    assert [n for _, n, _ in got["live"]] == names
    assert all(shape.startswith("f32[") and "{" not in shape
               for _, _, shape in got["live"])


def test_a_dump_without_scratch_reads_nothing():
    got = _tool().live_at_peak(_dump([], {}))
    assert (got["extent"], got["live_peak"], got["live"]) == (0, 0, [])
