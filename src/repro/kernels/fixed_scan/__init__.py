from .kernel import LANES, ROWS, advance_slots  # noqa: F401
