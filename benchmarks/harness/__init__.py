"""The yardstick: everything here is the benchmark's own and imports
nothing from the program under test (`paddle_tpu`). Builders and drivers
(sibling directories) are the only files that touch the program."""
