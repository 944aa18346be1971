"""Drivers, found by a mix's `kind`: `drivers/<kind>.py` with
`run(ctx) -> dict`. They drive the program through the handle a builder
returned and measure with the benchmark's own clock."""
