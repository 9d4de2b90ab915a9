"""XLA compiles the program counted inside the measured window
(``cxxnet_compiles_total`` after - before). Expected 0: every shape is
warmed up in set-up."""


def read(view):
    return view["compiles_in_window"]
