"""The yardstick: traffic, engine driver, trace reduction, work counts,
peaks, the plain reference and the comparison that decides `correct`.
Nothing here imports the program except `engine_driver.py` and `graph.py`."""
