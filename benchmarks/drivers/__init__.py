"""Drivers, found by a mix's `kind`: `drivers/<kind>.py` with
`run(ctx) -> dict` and `COMPARES`, the comparison that decides
`correct` for its cells ("served" or "trained": whether the reference
needs its training part). They drive the program through the handle a
builder returned and measure with the benchmark's own clock."""
