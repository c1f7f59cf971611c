"""What PR 69 decided about tier-1's clock, held so that a later PR cannot
undo it unseen: the persistent compile cache's minimum (measured: it stays
0.0), the limit a test,
``slow`` deselected, no file of ``tests/unit/`` with a cache switch of its
own, and the files ``tests/conftest.py`` runs first. No jax program."""

import configparser
import glob
import inspect
import os
import re
import sys

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
#: ``tests/conftest.py`` as pytest loaded it (an import of our own would run
#: its set-up a second time)
conftest = next(m for m in list(sys.modules.values())
                if getattr(m, "__file__", None)
                == os.path.join(ROOT, "tests", "conftest.py"))


def test_the_cache_minimum_is_the_one_stated_constant():
    """One stated constant, and it is what jax reads: 0.0, which PR 69
    measured against 1.0 on two groups of files, cold, and kept (a worker
    reads a one-operation program back in a quarter of its compile); no
    environment variable in its way."""
    assert conftest.CACHE_MIN_COMPILE_SECS == 0.0
    assert jax.config.jax_persistent_cache_min_compile_time_secs == \
        conftest.CACHE_MIN_COMPILE_SECS
    source = inspect.getsource(conftest)
    assert source.count("jax_persistent_cache_min_compile_time_secs") == 1
    assert not re.search(r"environ[^\n]*(MIN_COMPILE|CACHE_MIN|TEST_LIMIT)",
                         source)


def test_a_test_has_a_limit_of_300_seconds(request):
    assert conftest.TEST_LIMIT_SECS == 300
    limit = request.session._fixturemanager.getfixturedefs(
        "_test_limit", request.node)
    assert limit and limit[-1].scope == "function"
    assert "_test_limit" in request.fixturenames        # autouse: not asked
    assert "_test_limit" not in inspect.signature(
        test_a_test_has_a_limit_of_300_seconds).parameters
    source = inspect.getsource(conftest._test_limit)
    assert "dump_traceback_later" in source and "exit=True" in source
    assert "cancel_dump_traceback_later" in source


def test_slow_stays_deselected():
    ini = configparser.ConfigParser()
    ini.read(os.path.join(ROOT, "pytest.ini"))
    assert ini["pytest"]["addopts"].strip() == '-m "not slow"'
    assert "slow:" in ini["pytest"]["markers"]


#: the lines of ``tests/unit/`` that switch the compilation cache or set its
#: minimum, by file: the described-chip file's stated fixture (a TPU
#: executable cannot be read back without a chip), and two SCRIPTS that a
#: test hands to a child process (a cache of the child's own; a child that
#: must not read an executable this process cached)
OWN_CACHE_SETTINGS = {
    "tests/unit/ops/test_tpu_compile.py": 2,
    "tests/unit/test_setup_record.py": 1,
    "tests/unit/test_mixtral.py": 1,
}


def test_no_unit_file_switches_the_cache_on_its_own():
    setting = re.compile(
        r"update\(\s*\"(?:jax_enable_compilation_cache"
        r"|jax_persistent_cache_min_compile_time_secs)\"")
    found = {}
    for path in glob.glob(os.path.join(ROOT, "tests", "unit", "**", "*.py"),
                          recursive=True):
        if path == os.path.abspath(__file__):
            continue
        with open(path) as f:
            count = len(setting.findall(f.read()))
        if count:
            found[os.path.relpath(path, ROOT)] = count
    assert found == OWN_CACHE_SETTINGS


def test_the_files_run_first_exist():
    assert len(set(conftest.LONG_FILES_FIRST)) == len(
        conftest.LONG_FILES_FIRST) >= 5
    assert conftest.LONG_FILES_FIRST[0] == \
        "tests/unit/ops/test_tpu_compile.py"
    for path in conftest.LONG_FILES_FIRST:
        assert os.path.isfile(os.path.join(ROOT, path)), path
