"""Plain references: the answers of each operation computed with numpy
and pyarrow straight from the generated parquet. Nothing here imports
the program or reads anything it made, except the answers under test."""
