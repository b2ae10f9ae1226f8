"""The yardstick: traffic, timing, trace reduction, peaks and comparison.

Nothing here imports the program except `runners/`, which drive it.
"""
