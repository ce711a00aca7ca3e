"""No child of the agent-session runner outlives its run (PR 56): the
cases of test_bench_no_process_left.py (PR 55), which may not be edited
here, for the cell whose child is `serve_agent_holder.py`. The same
functions, handed the new cell."""

import signal

import pytest

import test_bench_no_process_left as base
from test_bench_no_process_left import checkout  # noqa: F401 - a fixture

CELL = "lfm2-24b-a2b.agent-sessions"


@pytest.mark.parametrize("sig", [signal.SIGTERM, signal.SIGKILL],
                         ids=["sigterm", "sigkill"])
@pytest.mark.parametrize("phase", list(base.PHASES))
def test_a_run_ended_from_outside_leaves_no_process(
        checkout, tmp_path, phase, sig):  # noqa: F811
    base.test_a_run_ended_from_outside_leaves_no_process(
        checkout, tmp_path, CELL, phase, sig)


def test_a_normal_run_ends_with_its_group_empty(checkout, tmp_path):  # noqa: F811
    base.test_a_normal_run_ends_with_its_group_empty(
        checkout, tmp_path, CELL)


def test_the_runner_starts_its_child_through_the_one_popen():
    from benchmark.runners import serve, serve_agent

    assert issubclass(serve_agent.Child, serve.Child)
    assert serve_agent.Child.script == "serve_agent_holder.py"
    assert set(vars(serve_agent.Child)) <= {
        "script", "__module__", "__doc__", "__qualname__",
        "__firstlineno__", "__static_attributes__"}
    src = open(serve_agent.__file__).read()
    assert "Popen" not in src and "import jax" not in src


def test_the_holder_is_tied_and_has_no_loop_of_its_own():
    """`test_every_child_is_tied_and_none_has_a_loop_of_its_own`'s
    rules for the eighth process that holds a chip (that test counts
    the `*_child.py` files, seven; this one is `serve_agent_holder.py`
    for that reason alone)."""
    import os

    from benchmark.runners import serve_agent

    src = open(os.path.join(os.path.dirname(serve_agent.__file__),
                            serve_agent.Child.script)).read()
    main = src[src.index("def main("):]
    tie = main.index("lifeline.tie_to_parent(args.parent_pid)")
    assert tie < main.index("import jax")
    assert tie < main.index("from benchmark import program")
    assert "import jax" not in src[:src.index("def main(")]
    assert "lifeline.serve_until_stopped(srv" in src
    assert "return lifeline.ORPHANED" in src
    assert "sys.stdin:" not in src and "def serve_commands" not in src
    assert "subprocess" not in src
