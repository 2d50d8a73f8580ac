"""Adapters from a configuration's sizes and a traffic mix to the program's
own step builders, one module per family of the program's registry. They
are the only part of the benchmark that imports the program."""
