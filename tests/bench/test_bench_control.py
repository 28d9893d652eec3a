"""The controls of ``correct`` at a size a test run holds: the program's
own bf16 path must come out not correct, and the program itself
correct, against the configuration's limits (set from the chip readings,
PERF.md section 2)."""
import pytest

from bench.control import fit_controls, fupdate_one_pass, serving_controls

from test_bench_cells import small_cell

CELLS = {"fraud.fit": ("f_rel", lambda ctx: fit_controls(ctx,
                                                          planted=False)),
         "fraud.events": ("kernel_sum_rel", serving_controls),
         "embed.batch": ("kernel_sum_rel", serving_controls)}


@pytest.fixture(scope="module", params=sorted(CELLS))
def readings(request):
    name = request.param
    number, controls = CELLS[name]
    _, _, ctx = small_cell(name, seconds=0.5, seed=77)
    return ctx, number, controls(ctx)


def test_program_reads_correct(readings):
    ctx, number, _ = readings
    assert ctx.correct, ctx.checks


def test_control_reads_not_correct(readings):
    ctx, number, ctl = readings
    assert ctx.config["control"] == "program_bf16"
    assert ctl["program_bf16"][number] > ctx.config["limits"][number], ctl


def test_planted_fupdate_fault_is_taken_out_again():
    """The planted one-pass ``fupdate`` refits the window's rows and
    leaves the kernel's precision as it found it."""
    from repro.kernels.fupdate import kernel
    real = kernel.mxu_precision
    _, _, ctx = small_cell("fraud.fit", seconds=0.5, seed=78)
    numbers = fupdate_one_pass(ctx)
    assert kernel.mxu_precision is real
    assert set(numbers) >= {"kkt_max", "f_rel", "not_converged"}
