"""The on-chip serving benchmark's yardstick.

Everything that decides a number lives here, beside the data it reads:
traffic generation (``traffic``), the wall-clock drive of the served path
(``loop``), the float32 reference and its comparison (``reference``,
``check``), operations and bytes from shapes (``ops``), the chip peaks
(``peaks``) and the reduction of a profiler trace (``trace``).  The program
under test is reached only through its public serving objects.
"""
