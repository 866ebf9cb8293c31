"""The check's control: the plain reference computed one precision step
below the configuration's, put in the program's place (``default``
precision: everything in bfloat16; ``highest``: three bfloat16 passes in
every product). At a size a test run holds it reads, on one of the
cell's numbers at least, ten times farther from the float32 reference
than the program does."""
import pytest

from bench import readings
from bench.tests.tiny import tiny_cell


@pytest.mark.parametrize("name,precision", [
    ("femnist_paper_full", "default"), ("femnist_paper_full", "highest"),
    ("femnist_xdevice_paging", "default"),
    ("femnist_xdevice_paging", "highest"),
    ("femnist_paper_sampled25", "default"),
    ("femnist_paper_sampled25", "highest")])
def test_control_reads_farther_from_the_reference_than_the_program(
        name, precision):
    cell = tiny_cell(name)
    cell.config["precision"]["matmul"] = precision
    prog = readings.program_reading(cell, 91)["numbers"]
    kind = readings.CONTROL[precision]
    (line,) = readings.control_readings(cell, 91, (kind,))
    assert line["kind"] == kind
    assert any(v > 10 * prog[k] for k, v in line["numbers"].items()), (
        line, prog)
