"""The roofline byte count, on a hand-worked catalog."""
import numpy as np

import bench_helpers  # noqa: F401  (puts the repo root on sys.path)
from bench import roofline
from bench.data import CatalogState

POLICY = {"scope": "type == file",
          "rules": [["big", "size > 1GB"], ["cold", "last_access > 180d"],
                    ["heavy_user", "owner == 'user3' and size > 16MB"]]}


def _state(size, atime, owner, is_dir):
    n = len(size)
    z = np.zeros(n, np.int64)
    return CatalogState(
        now=1.75e9, fid=np.arange(1, n + 1), size=np.asarray(size, np.int64),
        blocks=z, atime=np.asarray(atime, np.float64),
        mtime=np.asarray(atime, np.float64), owner=np.asarray(owner),
        group=z, is_dir=np.asarray(is_dir), hsm=z, subdir=z,
        path_fmt="/fs/{owner}/f{fid}")


def test_width_takes_the_narrowest_exact_encoding():
    assert roofline.width(np.array([0, 1])) == 1
    assert roofline.width(np.array([0, 255])) == 1
    assert roofline.width(np.array([-1, 127])) == 1
    assert roofline.width(np.array([0, 256])) == 2
    assert roofline.width(np.array([0, 70000])) == 4
    # 2**40 needs 8 bytes as an integer, but f32 holds it exactly
    assert roofline.width(np.array([0, 1 << 40])) == 4
    assert roofline.width(np.array([0, (1 << 40) + 1])) == 8
    assert roofline.width(np.array([0.5, 1.5])) == 2        # f16 holds it
    assert roofline.width(np.array([0.1])) == 8


def test_policy_bytes_on_a_hand_worked_catalog():
    # 4 rows; the policy reads type, size, last_access and owner
    st = _state(size=[1 << 30, 1 << 31, 5, 1 << 44],
                atime=[1.75e9, 1.7e9, 1.0e9, 1.75e9 - 128],
                owner=[0, 3, 199, 7], is_dir=[False, True, False, False])
    assert roofline.policy_columns(POLICY) == {"is_dir", "size", "atime",
                                               "owner"}
    # type 1 B, owner 1 B (<= 199), size 4 B (f32-exact up to 2**44),
    # atime 4 B (whole seconds below 2**32)
    assert roofline.row_bytes(st, POLICY) == 1 + 1 + 4 + 4
    assert roofline.policy_bytes(st, POLICY, matched=0) == 40
    assert roofline.policy_bytes(st, POLICY, matched=3) == 40 + 15


def test_count_follows_the_data_not_the_store():
    # a size that no 4-byte encoding holds makes the column 8 bytes wide
    st = _state(size=[(1 << 40) + 1, 3], atime=[1e9, 1e9], owner=[0, 1],
                is_dir=[False, False])
    assert roofline.row_bytes(st, POLICY) == 1 + 1 + 8 + 4
