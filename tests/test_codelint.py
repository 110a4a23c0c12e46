"""tools/codelint.py — the repo's own static-analysis gate (ISSUE 15).

Rule 1 keeps the compile-cache-token bug class extinct (PR 6
``quantize_min_size``: a BuildStrategy knob steering lowering but missing
from the token leaves stale executables live when the knob flips). Rule 2
catches free-floating locks in coordination code. Rule 4 keeps the op
modules off the environment (PR 29). All must be GREEN on the repo, and
all must be provably live — a synthetic violation injected into the
source must be caught.
"""
import os
import sys

import pytest

pytestmark = [pytest.mark.analysis]

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))
import codelint  # noqa: E402


def test_repo_is_clean():
    report = codelint.run_all()
    assert report["cache_token"] == [], report["cache_token"]
    assert report["free_floating_locks"] == [], \
        report["free_floating_locks"]
    assert report["failpoint_sites"] == [], report["failpoint_sites"]


def test_lint_sees_the_real_knobs():
    """Guard against the lint going blind: it must actually resolve the
    BuildStrategy knob set and the token closure on today's source."""
    import ast
    with open(codelint.COMPILER_PY) as f:
        tree = ast.parse(f.read())
    knobs = codelint._build_strategy_knobs(tree)
    for expected in ("quantize_min_size", "pp_stages", "numeric_policy",
                     "verify_program"):
        assert expected in knobs
    reads = codelint._knob_reads(tree, knobs)
    # the historic offender is read on the lowering path AND in the
    # token today — the exact configuration the lint certifies
    assert "quantize_min_size" in reads
    assert "numeric_policy" in reads


def test_synthetic_untokened_knob_read_is_caught():
    """Inject the PR 6/PR 13 bug shape: a new knob read on a lowering
    path without a token entry. The lint must flag exactly it."""
    with open(codelint.COMPILER_PY) as f:
        src = f.read()
    bad = src.replace(
        "        self.verify_program = _env_verify_default()",
        "        self.verify_program = _env_verify_default()\n"
        "        self.sneaky_knob = 3")
    bad = bad.replace(
        "    def _mesh_obj(self):",
        "    def _mesh_obj(self):\n"
        "        if getattr(self._build_strategy, 'sneaky_knob', 0):\n"
        "            pass\n")
    assert bad != src, "injection sites moved — update the test"
    violations = codelint.lint_cache_token(compiler_src=bad)
    assert len(violations) == 1 and "sneaky_knob" in violations[0]
    # ... and an allowlist entry silences it (the documented escape)
    allow = dict(codelint.TOKEN_ALLOWLIST)
    allow["sneaky_knob"] = "test"
    assert codelint.lint_cache_token(compiler_src=bad,
                                     allowlist=allow) == []


def test_synthetic_tokened_knob_is_clean():
    """The inverse: the same new knob read IS clean once _cache_token
    folds it in — the lint tracks the token's helper-call closure."""
    with open(codelint.COMPILER_PY) as f:
        src = f.read()
    bad = src.replace(
        "        self.verify_program = _env_verify_default()",
        "        self.verify_program = _env_verify_default()\n"
        "        self.sneaky_knob = 3")
    bad = bad.replace(
        "    def _mesh_obj(self):",
        "    def _mesh_obj(self):\n"
        "        if getattr(self._build_strategy, 'sneaky_knob', 0):\n"
        "            pass\n")
    fixed = bad.replace(
        "        return (tuple(sorted((bs.mesh_axes or {}).items())), "
        "bs.data_axis,",
        "        return (getattr(bs, 'sneaky_knob', None),\n"
        "                tuple(sorted((bs.mesh_axes or {}).items())), "
        "bs.data_axis,")
    assert fixed != bad, "token body moved — update the test"
    assert codelint.lint_cache_token(compiler_src=fixed) == []


def test_rebound_strategy_alias_is_still_seen():
    """REGRESSION: reading a knob through a fresh local binding
    (``cfg = self._build_strategy``) must not hide it from the lint."""
    with open(codelint.COMPILER_PY) as f:
        src = f.read()
    bad = src.replace(
        "        self.verify_program = _env_verify_default()",
        "        self.verify_program = _env_verify_default()\n"
        "        self.sneaky_knob = 3")
    bad = bad.replace(
        "    def _mesh_obj(self):",
        "    def _mesh_obj(self):\n"
        "        cfg = self._build_strategy\n"
        "        if cfg.sneaky_knob:\n"
        "            pass\n")
    assert bad != src, "injection sites moved — update the test"
    violations = codelint.lint_cache_token(compiler_src=bad)
    assert len(violations) == 1 and "sneaky_knob" in violations[0]


def test_free_floating_lock_is_caught(tmp_path):
    p = tmp_path / "mod.py"
    p.write_text(
        "import threading\n"
        "def racey():\n"
        "    with threading.Lock():\n"
        "        return 1\n")
    v = codelint.lint_free_floating_locks(paths=[str(p)])
    assert len(v) == 1 and "serializes nothing" in v[0]
    # a stored lock is the correct shape and stays clean
    q = tmp_path / "ok.py"
    q.write_text(
        "import threading\n"
        "_LOCK = threading.Lock()\n"
        "def fine():\n"
        "    with _LOCK:\n"
        "        return 1\n")
    assert codelint.lint_free_floating_locks(paths=[str(q)]) == []


def test_cli_exit_codes(tmp_path, capsys):
    assert codelint.main(["--json"]) == 0
    out = capsys.readouterr().out
    assert '"ok": true' in out


def test_failpoint_site_catalog_matches_runtime():
    """Rule 3's AST-parsed catalog and the live SITES registry must be
    the same set — a drift here means the lint guards a phantom."""
    from paddle_tpu.framework import faultinject
    assert codelint._site_catalog() == set(faultinject.SITES)


def test_uncatalogued_failpoint_site_is_caught(tmp_path):
    p = tmp_path / "mod.py"
    p.write_text(
        "from paddle_tpu.framework import faultinject\n"
        "def f():\n"
        "    faultinject.hit('io.not_a_real_site')\n")
    v = codelint.lint_failpoint_sites(paths=[str(p)])
    assert len(v) == 1 and "names a site missing" in v[0]
    # the short alias used in hot modules is linted too
    q = tmp_path / "alias.py"
    q.write_text(
        "from paddle_tpu.framework import faultinject as fi\n"
        "def f():\n"
        "    fi.hit('serving.not_a_real_site')\n")
    v = codelint.lint_failpoint_sites(paths=[str(q)])
    assert len(v) == 1 and "names a site missing" in v[0]


def test_computed_failpoint_site_is_caught(tmp_path):
    p = tmp_path / "mod.py"
    p.write_text(
        "from paddle_tpu.framework import faultinject\n"
        "def f(which):\n"
        "    faultinject.hit('io.' + which)\n")
    v = codelint.lint_failpoint_sites(paths=[str(p)])
    assert len(v) == 1 and "string literal" in v[0]
    # a catalogued literal site is clean
    q = tmp_path / "ok.py"
    q.write_text(
        "from paddle_tpu.framework import faultinject\n"
        "def f():\n"
        "    faultinject.hit('transport.send')\n")
    assert codelint.lint_failpoint_sites(paths=[str(q)]) == []


def test_no_op_module_reads_the_environment():
    assert codelint.lint_ops_environment() == []
    # ... and the lint is not blind: it finds the three reads it allows
    assert len(codelint.lint_ops_environment(allowlist={})) == 3


@pytest.mark.parametrize("source", [
    "import os\nIMPL = os.environ.get('PADDLE_TPU_ATTN_IMPL', 'auto')\n",
    "import os\nBLOCK = os.environ['PADDLE_TPU_FLASH_BLOCK_Q']\n",
    "import os\nBLOCK = os.getenv('PADDLE_TPU_FLASH_BLOCK_K')\n",
    "from os import environ\nON = 'PADDLE_TPU_X' in environ\n",
    "import os\ndef f(name):\n    return os.environ.get('PADDLE_' + name)\n",
])
def test_an_environment_read_in_an_op_module_is_caught(source):
    v = codelint.lint_ops_environment(sources={"ops/mod.py": source})
    assert len(v) == 1 and "reads the environment" in v[0]


def test_an_allow_listed_environment_read_passes():
    source = ("import os\n"
              "def default_interpret():\n"
              "    return os.environ.get('PADDLE_TPU_PALLAS_INTERPRET')\n")
    assert codelint.lint_ops_environment(sources={"ops/mod.py": source}) \
        == []
    assert len(codelint.lint_ops_environment(
        sources={"ops/mod.py": source}, allowlist={})) == 1
