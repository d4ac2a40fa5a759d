"""Window drivers: one file per way of driving the program."""
