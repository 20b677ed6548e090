"""The copied collective parser (lib/hlo.py) on a canned HLO module."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from lib import hlo  # noqa: E402

CANNED = """HloModule step

%body (p: (s32[], bf16[1024])) -> (s32[], bf16[1024]) {
  %p = (s32[], bf16[1024]) parameter(0)
  %x = bf16[1024]{0} get-tuple-element(%p), index=1
  %ar = bf16[1024]{0} all-reduce(%x), replica_groups={{0,1,2,3}}, to_apply=%add
  ROOT %t = (s32[], bf16[1024]) tuple(%i, %ar)
}

%cond (p: (s32[], bf16[1024])) -> pred[] {
  ROOT %c = pred[] constant(true)
}

ENTRY %main (a: bf16[409600000], u: s32[640]) -> bf16[409600000] {
  %a = bf16[409600000]{0} parameter(0)
  %u = s32[640]{0} parameter(1)
  %big = bf16[409600000]{0} all-reduce(%a), replica_groups={{0,1,2,3}}, to_apply=%add
  %ags = (s32[640]{0}, s32[2560]{0}) all-gather-start(%u), replica_groups={{0,1,2,3}}, dimensions={0}
  %agd = s32[2560]{0} all-gather-done(%ags)
  %w = (s32[], bf16[1024]) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"20"}}
  ROOT %r = bf16[409600000]{0} copy(%big)
}
"""


def test_collective_counts_and_operand_bytes():
    out = hlo.collectives(CANNED)
    # one entry all-reduce plus 20 trips of the loop's
    assert out["count"] == {"all-reduce": 21.0, "all-gather": 1.0}
    assert out["bytes"]["all-reduce"] == 409600000 * 2 + 20 * 1024 * 2
    # the -start tuple's result half (2560 s32) over the group of 4
    assert out["bytes"]["all-gather"] == 640 * 4
    assert out["total_bytes"] == pytest.approx(
        409600000 * 2 + 20 * 2048 + 2560)


def test_group_size_spellings():
    assert hlo.group_size("replica_groups={{0,1},{2,3}}") == 2
    assert hlo.group_size("replica_groups={0,1,2}") == 3
    assert hlo.group_size("replica_groups=[2,4]<=[8]") == 4
