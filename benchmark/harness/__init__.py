"""The yardstick, as far as it is the same for every architecture: traffic,
engine driver, trace reduction, peaks, the control precisions, the seeded
draw of weights, and the sampling and gap arithmetic of the comparison that
decides `correct`. What belongs to one architecture (its weight tree, its
graph, its plain reference, its count of work) is a directory under
`benchmark/families/`, found by the configuration's `model_type`
(`family.py`). Nothing here imports the program except `engine_driver.py`."""
