"""Direct unit tests for the compiled-HLO collective parser
(``utils/hlo.py``) on synthetic HLO text — previously exercised only
through the 8-device ``verify_sharded`` subprocess sweep.  The byte
model and the regex shapes are the contract the sharding analyzers
(``analysis.collectives``) and the merged report's sharding cells
build on."""
import pytest

from repro.utils.hlo import collective_bytes, collective_kinds


def test_all_gather_output_bytes():
    hlo = ("  %ag = u32[8,16]{1,0} all-gather(u32[2,16]{1,0} %p), "
           "dimensions={0}\n")
    assert collective_kinds(hlo) == {"all-gather": 1}
    got = collective_bytes(hlo)
    assert got["all-gather"] == 8 * 16 * 4        # u32 output bytes
    assert got["total"] == got["all-gather"]


def test_all_reduce_double_counted():
    # all-reduce ~ reduce-scatter + all-gather ring: 2x the bytes.
    hlo = "  %ar = f32[128]{0} all-reduce(f32[128]{0} %x), to_apply=%a\n"
    got = collective_bytes(hlo)
    assert got["all-reduce"] == 2 * 128 * 4


def test_tuple_shaped_collective():
    hlo = ("  %cp = (f32[64]{0}, f32[64]{0}) collective-permute("
           "f32[64]{0} %x, f32[64]{0} %y)\n")
    assert collective_kinds(hlo) == {"collective-permute": 1}
    assert collective_bytes(hlo)["collective-permute"] == 2 * 64 * 4


def test_async_start_variant_matches():
    hlo = ("  %ag = bf16[32,8]{1,0} all-gather-start(bf16[4,8]{1,0} %p), "
           "dimensions={0}\n")
    assert collective_kinds(hlo) == {"all-gather": 1}
    assert collective_bytes(hlo)["all-gather"] == 32 * 8 * 2


@pytest.mark.parametrize("hlo, kinds, nbytes", [
    ("  %a = u32[16]{0} all-gather(u32[4]{0} %p), dimensions={0}\n"
     "  %b = u32[8]{0} all-gather(u32[2]{0} %q), dimensions={0}\n"
     "  %c = s32[4]{0} reduce-scatter(s32[16]{0} %r), to_apply=%add\n",
     {"all-gather": 2, "reduce-scatter": 1},
     {"all-gather": (16 + 8) * 4, "reduce-scatter": 4 * 4}),
    # One of each kind, operands without shapes; all-reduce is an async
    # start and counts 2x (ring factor), all-to-all sums its tuple.
    ("  %ag = bf16[16,128]{1,0} all-gather(%x), replica_groups={}\n"
     "  %ar = f32[64]{0} all-reduce-start(%y), to_apply=%sum\n"
     "  %rs = f32[8,8]{1,0} reduce-scatter(%z), dimensions={0}\n"
     "  %a2a = (f32[4,4]{1,0}, f32[4,4]{1,0}) all-to-all(%p, %q)\n",
     {"all-gather": 1, "all-reduce": 1, "reduce-scatter": 1,
      "all-to-all": 1},
     {"all-gather": 16 * 128 * 2, "all-reduce": 64 * 4 * 2,
      "reduce-scatter": 64 * 4, "all-to-all": 2 * 16 * 4}),
], ids=["all-gather+reduce-scatter", "one-of-each"])
def test_mixed_module_accumulates_per_kind(hlo, kinds, nbytes):
    assert collective_kinds(hlo) == kinds
    got = collective_bytes(hlo)
    assert {k: v for k, v in got.items() if k != "total"} == nbytes
    assert got["total"] == sum(nbytes.values())


def test_non_collective_ops_ignored():
    hlo = ("  %d = f32[1024]{0} dot(f32[1024,64]{1,0} %w, f32[64]{0} %x)\n"
           "  %g = u32[8]{0} gather(u32[64]{0} %t, s32[8]{0} %i)\n")
    assert collective_kinds(hlo) == {}
    assert collective_bytes(hlo)["total"] == 0.0
