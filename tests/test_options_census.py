"""Census of the `CFS_*` environment names (ROADMAP C4).

The count of distinct names in `chubaofs_tpu/` + `bench.py` is written here,
so a new name has to change it in the open; the names PR 29 removed (each had
one value in use anywhere in the tree) may not come back under the package.
Same count as `grep -rhoE 'CFS_[A-Z0-9_]+' chubaofs_tpu bench.py | sort -u | wc -l`.
"""

import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
NAME = re.compile(r"CFS_[A-Z0-9_]+")

DISTINCT_NAMES = 73

REMOVED = (
    "CFS_EVLOOP", "CFS_EVLOOP_HTTP",
    "CFS_RPC_POOL", "CFS_RPC_POOL_SIZE", "CFS_RPC_POOL_TTL",
    "CFS_PIPELINE_WINDOW", "CFS_PUT_ENCODE_AHEAD",
    "CFS_PROXY_ACTIVE_VOLS", "CFS_CACHE_ADMIT",
)


def _names(*paths: pathlib.Path) -> dict[str, str]:
    """name -> the first file it appears in."""
    seen: dict[str, str] = {}
    for p in paths:
        for name in NAME.findall(p.read_text(encoding="utf-8")):
            seen.setdefault(name, str(p.relative_to(ROOT)))
    return seen


@pytest.fixture(scope="module")
def package_names():
    return _names(*sorted((ROOT / "chubaofs_tpu").rglob("*.py")))


@pytest.mark.parametrize("name", REMOVED)
def test_removed_name_stays_out_of_the_package(name, package_names):
    assert name not in package_names, \
        f"{name} is back in {package_names[name]}: one value was in use " \
        "anywhere, so it is a constant (ISSUE 29)"


def test_distinct_name_count_is_the_one_written_here(package_names):
    names = set(package_names) | set(_names(ROOT / "bench.py"))
    assert len(names) == DISTINCT_NAMES, (
        f"{len(names)} distinct CFS_* names, {DISTINCT_NAMES} written in "
        "this test: a new option needs two callers that want different "
        "values (ROADMAP C4), and changes this number in the open. "
        f"Names: {sorted(names)}")
