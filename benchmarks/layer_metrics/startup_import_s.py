"""Start-up phase ``import`` of the server's process
(``stpu_startup_seconds_total{phase=import}`` at the window's first
scrape): the kernel's start of the server's process -> its first device
query returned: the interpreter, the imports, the back end's start.
With the other three it splits ``ready_s`` from inside the process.
None on a program without the series."""
from benchmarks.layer_metrics import _window

NAME, UNIT, BETTER = "startup_import_s", "s", "lower"
LAYER = "entry"
MOVES = "setup_s"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def compute(run):
    return _window.startup_s(run, "import")
