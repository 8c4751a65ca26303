"""The control, the references one precision step down in the program's
place, fails the cell's limits (here at a test's size; on the chip at
the cell's size, with ``bench/control.py``)."""

from small import small_run


def test_sim_control_fails():
    run, system = small_run("sim.fattree64.rag", seed=77)
    got = system.control(run)
    lim = run.config["limits"]
    print(got, run.checks)
    assert got["cost_rel_err"] > lim["cost_rel_err"] or \
        got["winner_mismatch"] > lim["winner_mismatch"]
    assert got["rate_rel_err"] > lim["rate_rel_err"]
