"""The chip benchmark's yardstick: cell discovery, drivers, trace reduction,
counting functions, peak table, plain references and the comparison that
decides ``correct``. Nothing here is imported by the program under test."""
