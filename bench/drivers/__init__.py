"""One driver per kind of traffic; a cell file names its driver by ``kind``."""
