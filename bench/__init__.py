"""The chip benchmark of the one-class slab SVM (see ``bench/run.py``)."""
