"""The program steps the window drives, built by the program's builders."""
